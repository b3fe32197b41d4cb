"""Run every benchmark: one per paper table/figure + engines + roofline.

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable tables).
``--smoke`` runs a 1-config CI subset (rq3 + event_pipeline) so call-site
migrations can't silently break the benchmark suite.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    agg_engine_bench,
    event_pipeline_bench,
    roofline,
    rq1_idle,
    rq1b_lambda,
    rq2_shard_ablation,
    rq2b_lambda_sweep,
    rq3_cross_arch,
    smoke_invariants,
)
from benchmarks.common import header, write_json

BENCHES = [
    ("rq1_idle (Table III)", rq1_idle.main),
    ("rq1b_lambda (Table IV)", rq1b_lambda.main),
    ("rq2_shard_ablation (Table V)", rq2_shard_ablation.main),
    ("rq2b_lambda_sweep (Table VI)", rq2b_lambda_sweep.main),
    ("rq3_cross_arch (Table VII)", rq3_cross_arch.main),
    ("agg_engine (engines)", agg_engine_bench.main),
    ("event_pipeline (schedules)", event_pipeline_bench.main),
    ("roofline (§Roofline)", roofline.main),
    ("smoke_invariants (CI gate input)", smoke_invariants.main),
]


SMOKE_BENCHES = [
    ("rq3_cross_arch (smoke)", lambda: rq3_cross_arch.main(smoke=True)),
    ("event_pipeline (smoke)",
     lambda: event_pipeline_bench.main(["--smoke"])),
    ("roofline host fold (smoke)",
     lambda: roofline.host_fold_main(smoke=True)),
    ("smoke_invariants (CI gate input)", smoke_invariants.main),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: 1-config rq3 + event_pipeline + "
                         "host-fold roofline + smoke invariants")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows + invariants as a JSON artifact "
                         "(fed to benchmarks.check_invariants in CI)")
    args = ap.parse_args(argv)
    header()
    benches = SMOKE_BENCHES if args.smoke else BENCHES
    failures = []
    for name, fn in benches:
        print(f"\n{'='*72}\n== {name}\n{'='*72}")
        try:
            fn()
        except Exception as e:
            failures.append((name, e))
            traceback.print_exc()
    if args.json:
        write_json(args.json)
    if failures:
        print(f"\n{len(failures)} benchmark(s) FAILED: "
              f"{[n for n, _ in failures]}")
        sys.exit(1)
    print(f"\nAll {len(benches)} benchmarks passed.")


if __name__ == "__main__":
    main()

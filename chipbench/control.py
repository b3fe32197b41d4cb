"""The control of the comparison that decides ``correct``.

The reference is put in the program's place and computed one precision
below the configuration's f32: every received contribution is cast to
bfloat16 on the device, folded left in bfloat16 and divided by N in
bfloat16. A run of the harness with this in place of the session must come
out not correct; the gaps it reads are the upper readings the comparison's
limit is set below. On the chip, at a cell's own size:

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

One process runs every seed. It prints one result line per seed, as a run
of ``chipbench/run.py`` would, and exits 0 only if every seed came out not
correct. The benchmark's own runs never run it.
"""

from __future__ import annotations

import os
import sys
import time

T_PROCESS = time.perf_counter()

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Answer:
    kernel_folds = None

    def __init__(self, avg: np.ndarray):
        self.avg_flat = avg


class Bf16Session:
    """Stands in for ``FederatedSession``: the reference's mean in bfloat16."""

    def __init__(self, cell):
        from chipbench import reference

        self._m = int(cell.config["session"]["n_shards"])
        self._codec = reference.load_codec(cell.traffic["session"]["codec"])
        self._params = cell.traffic["codec_params"]

    def round(self, grads):
        import jax.numpy as jnp

        from chipbench import reference

        acc = None
        for g in grads:
            x = jnp.asarray(reference.received(g, self._m, self._codec, self._params))
            x = x.astype(jnp.bfloat16)
            acc = x if acc is None else acc + x
        avg = (acc / jnp.asarray(len(grads), jnp.bfloat16)).astype(jnp.float32)
        return _Answer(np.asarray(avg))


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description="Run the bfloat16 control of a cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    harness.strip_program_env()
    try:
        cell = harness.load_cell(args.workload)
        harness.enable_compile_cache()
        devices, peak = harness.check_device(cell.chips)
    except (harness.BenchError, OSError) as e:
        print(f"chipbench control: {e}", file=sys.stderr)
        return 2
    caught = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    t_start = T_PROCESS
    for seed in seeds:
        result = harness.run_cell(cell, seed, args.seconds, False, t_process=t_start,
                                  devices=devices, peak=peak, session_factory=Bf16Session)
        t_start = time.perf_counter()
        caught += not result["correct"]
        print(f"control seed {seed}: correct={result['correct']} "
              f"max_ulp_gap={result['checks']['max_ulp_gap']['value']}")
        print(json.dumps(result), flush=True)
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())

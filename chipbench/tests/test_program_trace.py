"""CPU tests of the program-span reduction, ``chipbench/program_trace.py``."""

from __future__ import annotations

import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from chipbench import program_trace as pt  # noqa: E402
from chipbench import trace as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "chipbench", "tests", "two_rounds.xplane.pb")
#: one top-k round (N=3, 200,003 elements, M=8) recorded on a TPU v5e with
#: the program's spans
SPANS_FIXTURE = os.path.join(ROOT, "chipbench", "tests", "one_topk_round.xplane.pb")


def synthetic():
    """Two rounds of 100 ns; in the first a fold window, in the second an
    encode split in two spans and one more host-to-device copy."""
    ev, pe = tr.Event, pt.Event
    spans = [ev("round", 0, 100), ev("round", 100, 200),
             ev("end_round", 40, 100), ev("fedavg_multi", 50, 95)]
    devices = {"/device:TPU:0": [ev("%a", 10, 20), ev("%fedavg_stream.1", 82, 84),
                                 ev("%a", 120, 130)]}
    program = [
        pe("repro.session.round", 0, 100, {"rnd": 0, "n": 3}),
        pe("repro.fold.window", 50, 95, {"index": 0, "n": 3, "cols": 4096}),
        pe("repro.fold.fill", 50, 60, {"bytes": 1000}),
        pe("repro.fold.h2d", 60, 80, {"bytes": 1000}),
        pe("repro.fold.kernel", 80, 85),
        pe("repro.fold.d2h", 85, 90, {"bytes": 100}),
        pe("repro.fold.divide", 90, 95),
        pe("repro.round.program", 130, 150),
        pe("repro.codec.encode", 130, 140, {"elems": 7}),
        pe("repro.codec.encode", 140, 150, {"elems": 7}),
        pe("repro.fold.h2d", 160, 170, {"bytes": 3000}),
    ]
    return devices, spans, program


def test_program_reducer_sums_per_round_and_cuts_gaps():
    devices, spans, program = synthetic()
    red = pt.reduce(devices, spans, program)
    assert red.rounds == 2 and red.busy_s == pytest.approx(22e-9)
    assert red.span_s("fold.h2d") == pytest.approx([20e-9, 10e-9])
    assert red.span_s("codec.encode") == pytest.approx([0.0, 20e-9])
    assert red.stat_sum("fold.h2d", "bytes") == [1000, 3000]
    assert red.stat_sum("codec.encode", "elems") == [0, 14]
    assert red.span_s("round.readback") is None
    assert red.stat_sum("round.readback", "bytes") is None
    # each gap cut at program-span boundaries, each piece to the innermost
    # span over it; the two encodes' pieces join; uncovered pieces keep the
    # harness's owners
    totals = {k: v * 1e9 for k, v in red.gap_totals().items()}
    assert totals == pytest.approx({
        "repro.session.round": 45, "driver": 60, "repro.fold.h2d": 30,
        "repro.codec.encode": 20, "repro.fold.fill": 10, "repro.fold.d2h": 5,
        "repro.fold.divide": 5, "repro.fold.kernel": 3})
    assert sorted(s * 1e9 for o, s in red.gaps if o == "repro.codec.encode") \
        == pytest.approx([20])
    assert sum(s for _, s in red.gaps) + red.busy_s == pytest.approx(red.window_s)
    assert [s for _, s in red.gaps] == sorted((s for _, s in red.gaps), reverse=True)


def test_program_reducer_without_program_spans_is_the_harness_reducer():
    devices, spans, _ = synthetic()
    recorded = tr.load(FIXTURE)
    assert pt.load(FIXTURE) == []
    for args in [(devices, spans), recorded]:
        want = tr.reduce(*args)
        got = pt.reduce(*args, [])
        for f in dataclasses.fields(want):
            assert getattr(got, f.name) == getattr(want, f.name), f.name
        assert got.breakdown() == want.breakdown()
        assert got.span_s("fold.h2d") is None


def test_split_reads_the_program_spans():
    devices, spans, program = synthetic()
    out = pt.split(pt.reduce(devices, spans, program), spans)
    assert out["rounds"] == 2
    assert out["span_s_per_round"]["fold.h2d"] == pytest.approx(15e-9)
    assert out["fold_parts_s_per_round"] == pytest.approx(27.5e-9)
    assert out["harness_fedavg_multi_s_per_round"] == pytest.approx(22.5e-9)
    assert out["h2d_gbps"] == pytest.approx(4000 / 30e-9 / 1e9)
    assert out["idle_owned_by_program"] == pytest.approx(118 / 178)
    bare = pt.split(pt.reduce(devices, spans, []), spans)
    assert bare["span_s_per_round"] == {} and bare["h2d_gbps"] is None
    assert bare["idle_owned_by_program"] == 0


def test_program_reducer_on_a_recorded_tpu_trace():
    assert os.path.getsize(SPANS_FIXTURE) < 1 << 20
    devices, spans = tr.load(SPANS_FIXTURE)
    program = pt.load(SPANS_FIXTURE)
    names = {e.name for e in program}
    assert names == {pt.PROGRAM_PREFIX + n for n in (
        "session.round", "round.program", "codec.encode", "round.upload", "round.phases",
        "engine.end_round", "round.readback", "fold.window", *pt.FOLD_PARTS)}
    red = pt.reduce(devices, spans, program)
    assert red.rounds == 1 and red.kernel_s("fedavg_stream") > 0
    assert red.kernel_s("topk_sparsify") > 0
    assert red.stat_sum("codec.encode", "elems") == [3 * 200_003]
    assert red.stat_sum("round.readback", "bytes") == [200_003 * 4]
    (window,) = [e for e in program if e.name == "repro.fold.window"]
    cols = window.stats["cols"]
    assert cols % 4096 == 0 and window.stats["n"] == 3
    assert red.stat_sum("fold.h2d", "bytes") == red.stat_sum("fold.fill", "bytes") \
        == [3 * cols * 4]
    assert red.stat_sum("fold.d2h", "bytes") == [cols * 4]
    out = pt.split(red, spans)
    # the parts nest in the window, the window in the harness's span; at
    # this size the span's set-up outside the window is a few percent of it
    parts, window_s = out["fold_parts_s_per_round"], red.span_s("fold.window")[0]
    assert 0.95 * window_s < parts <= window_s <= out["harness_fedavg_multi_s_per_round"]
    assert out["idle_owned_by_program"] > 0.95
    assert out["h2d_gbps"] > 0
    assert sum(s for _, s in red.gaps) + red.busy_s == pytest.approx(red.window_s)

"""CPU tests of the chip benchmark: ``pytest chipbench/tests``.

They cover what a run does apart from the chip: finding every file by
name, the seeded contribution sets, the trace reduction (on a trace
recorded from two tiny top-k rounds on a TPU v5e), the reference and its
comparison, the refusal to run without a listed TPU, and whole runs with
the device check skipped: sound ones come out correct, and the control and
each fault a cell can have come out not correct.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from chipbench import control, harness, reference  # noqa: E402
from chipbench import trace as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "chipbench", "tests", "two_rounds.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
PEAK = {"hbm_bytes_per_s": 819e9}


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny(name: str, n: int = 4, elems: int = 20_011) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, n_clients=n, grad_elems=elems)
    return cell


def run(cell, trace=False, seconds=0.2, seed=2**31 + 11, **kw) -> dict:
    return harness.run_cell(cell, seed, seconds, trace, t_process=time.perf_counter(),
                            peak=PEAK, log=lambda *_: None, **kw)


# -- files found by name ------------------------------------------------------

def test_every_file_loads_by_name():
    b = bench()
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert cell.traffic["name"] == w["traffic"]
        assert reference.load_codec(cell.traffic["session"]["codec"]).apply
        assert cell.per_layer
    for m in b["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)
    assert harness.load_json("peaks.json")["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_keeps_its_contract():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert [e["name"] for e in b["end_to_end"]] == ["round_s", "setup_s"]
    assert [w["name"] for w in b["workloads"]] == [
        "vgg16-n20-m8.identity", "resnet18-n20-m8.identity", "resnet18-n20-m8.topk"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in b[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for e in b["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25 and e["source"] == "host_clock"
    for m in b["per_layer"]:
        assert m["moves"] == "round_s" and "bound" not in m
    codec = [m for m in b["per_layer"] if m["name"] == "codec_kernel_ms"][0]
    assert codec["workloads"] == ["resnet18-n20-m8.topk"]
    assert all(w["chips"] == 1 for w in b["workloads"])


def test_program_env_is_stripped(monkeypatch):
    monkeypatch.setenv("REPRO_AGG_ENGINE", "streaming")
    monkeypatch.setenv("REPRO_AGG_WORKERS", "1")
    harness.strip_program_env()
    assert not [k for k in os.environ if k.startswith("REPRO_")]


# -- contributions --------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_sets_are_seeded_and_distinct(seed):
    a1, a2 = harness.make_set(3, 1000, seed, 0), harness.make_set(3, 1000, seed, 0)
    b = harness.make_set(3, 1000, seed, 1)
    assert all(np.array_equal(x, y) for x, y in zip(a1, a2))
    assert all(x.dtype == np.float32 and x.shape == (1000,) for x in a1)
    assert not any(np.array_equal(x, y) for x, y in zip(a1, b))
    assert not np.array_equal(a1[0], a1[1])
    other = harness.make_set(3, 1000, seed + 1, 0)
    assert not np.array_equal(a1[0], other[0])


# -- trace reduction --------------------------------------------------------------

def test_reducer_on_a_recorded_tpu_trace():
    assert os.path.getsize(FIXTURE) < 1 << 20
    red = tr.reduce(*tr.load(FIXTURE))
    assert red.rounds == 2 and red.devices == 1
    assert 0 < red.busy_s < red.window_s
    fold, topk = red.kernel_s("_fold_sum"), red.kernel_s("_topk_flat")
    assert fold > 0 and topk > fold
    assert red.kernel_s("no_such_kernel") is None
    idle = sum(s for _, s in red.gaps)
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-9)
    assert {owner for owner, _ in red.gaps} <= {"driver", "end_round", "fedavg_multi"}
    assert "fedavg_multi" in red.gap_totals()
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "%_topk_flat.1"
    assert [s for _, s in bd["idle_gaps"]] == sorted((s for _, s in bd["idle_gaps"]), reverse=True)


def test_reducer_busy_union_and_gap_owners():
    ev = tr.Event
    spans = [ev("round", 0, 100), ev("end_round", 40, 100), ev("fedavg_multi", 50, 90)]
    devices = {"/device:TPU:0": [ev("%a", 10, 20), ev("%b", 15, 30), ev("%a", 60, 70),
                                 ev("%c", 95, 130)]}
    red = tr.reduce(devices, spans)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx((20 + 10 + 5) * 1e-9)
    assert red.op_s == pytest.approx({"%a": 20e-9, "%b": 15e-9, "%c": 5e-9})
    assert [owner for owner, _ in red.gaps] == ["end_round", "fedavg_multi", "driver"]
    assert [s for _, s in red.gaps] == pytest.approx([30e-9, 25e-9, 10e-9])


def test_metric_readers_on_the_recorded_trace():
    red = tr.reduce(*tr.load(FIXTURE))
    cfg = {"n_clients": 3, "grad_elems": 200_003}
    rounds = [{"wall_s": 0.05, "end_round": 0.01, "fedavg_multi": 0.008}] * 2
    r = harness.Run(cfg, PEAK, rounds, red, 123_000_000)
    read = {m["name"]: harness.load_reader(m["name"]).read(r) for m in bench()["per_layer"]}
    assert read["driver_s"] == pytest.approx(0.04)
    assert read["engine_s"] == pytest.approx(0.002)
    assert read["fold_windows_s"] == pytest.approx(0.008)
    assert read["fold_kernel_ms"] == pytest.approx(red.kernel_s("_fold_sum") / 2 * 1e3)
    assert read["codec_kernel_ms"] == pytest.approx(red.kernel_s("_topk_flat") / 2 * 1e3)
    assert 0 < read["fold_roofline"] <= 100
    assert 0 < read["device_idle_share"] < 100
    assert read["peak_hbm_gb"] == pytest.approx(0.123)
    empty = harness.Run(cfg, PEAK, [{"wall_s": 1.0}], None, None)
    assert all(harness.load_reader(m["name"]).read(empty) is None for m in bench()["per_layer"])


# -- the reference and the comparison -------------------------------------------

def test_comparator_rejects_one_ulp_and_a_bf16_fold():
    rows = harness.make_set(5, 50_000, 3, 0)
    ref = reference.mean_f32(rows)
    assert reference.ulp_gap(ref.copy(), ref)["max_ulp"] == 0
    bumped = ref.copy()
    bumped[123] = np.nextafter(bumped[123], np.float32(np.inf))
    gap = reference.ulp_gap(bumped, ref)
    assert gap["max_ulp"] == 1 and gap["differing"] == 1
    import ml_dtypes

    acc = rows[0].astype(ml_dtypes.bfloat16)
    for r in rows[1:]:
        acc = acc + r.astype(ml_dtypes.bfloat16)
    bf16 = (acc / ml_dtypes.bfloat16(len(rows))).astype(np.float32)
    assert reference.ulp_gap(bf16, ref)["max_ulp"] > 1000


def test_comparator_edge_cases():
    ref = np.array([0.0, 1.0, -2.0], np.float32)
    assert reference.ulp_gap(np.array([-0.0, 1.0, -2.0], np.float32), ref)["max_ulp"] == 0
    assert reference.ulp_gap(np.array([0.0, np.nan, -2.0], np.float32), ref)["max_ulp"] \
        == reference.NO_ANSWER
    assert reference.ulp_gap(ref[:2], ref)["max_ulp"] == reference.NO_ANSWER
    assert reference.ulp_gap(ref.astype(np.float64), ref)["max_ulp"] == reference.NO_ANSWER
    neg = np.array([0.0, 1.0, np.nextafter(np.float32(-2.0), np.float32(0))], np.float32)
    assert reference.ulp_gap(neg, ref)["max_ulp"] == 1


def test_reference_fold_is_the_left_fold():
    rows = harness.make_set(6, 30_001, 9, 1)
    acc = rows[0].copy()
    for r in rows[1:]:
        acc += r
    assert np.array_equal(reference.mean_f32(rows), acc / np.float32(6))


def test_shard_bounds_split_evenly():
    assert reference.shard_bounds(10, 3) == [(0, 4), (4, 7), (7, 10)]
    assert reference.shard_bounds(16, 8)[-1] == (14, 16)


def test_topk_reference_agrees_with_the_program_mirror():
    from repro.core import wire_codec

    params = harness.load_json("traffic", "topk.json")["codec_params"]
    topk = reference.load_codec("topk")
    x = harness.make_set(1, 3 * 4096 + 77, 5, 0)[0]
    got = topk.apply(x, params)
    assert np.array_equal(got, wire_codec.topk_numpy(x, params["k_per_tile"]))
    kept = np.count_nonzero(got[:4096])
    assert params["k_per_tile"] <= kept < 2 * params["k_per_tile"]


# -- no TPU, no listed chip, no program -----------------------------------------

def test_no_tpu_fails():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.check_device(1)


def test_unlisted_device_kind_fails(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(harness.BenchError, match="not in chipbench/peaks.json"):
        harness.check_device(1)
    with pytest.raises(harness.BenchError, match="needs 4 chips"):
        harness.check_device(4)


def _cli(cwd: str) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if not k.startswith("REPRO_")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "resnet18-n20-m8.identity",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_on_cpu_exits_nonzero_without_a_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_cli_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


# -- whole runs with the device check skipped -------------------------------------

@pytest.mark.parametrize("name", ["resnet18-n20-m8.identity", "resnet18-n20-m8.topk"])
@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(name, trace):
    res = run(tiny(name), trace=trace)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 2
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_ulp_gap"] == {"value": 0, "limit": 0}
    if trace:
        assert {"driver_s", "engine_s"} <= set(res["metrics"])
        assert "round_s" not in res["metrics"]
    else:
        assert set(res["metrics"]) == {"round_s", "setup_s"}
        assert res["metrics"]["round_s"]["value"] > 0


@pytest.mark.parametrize("name", ["resnet18-n20-m8.identity", "resnet18-n20-m8.topk"])
def test_control_is_not_correct(name):
    res = run(tiny(name), session_factory=control.Bf16Session)
    assert not res["correct"] and res["failed"] == res["attempted"]
    assert res["checks"]["max_ulp_gap"]["value"] > 1000


class _Broken:
    """A session whose rounds are wrong in one way."""

    def __init__(self, cell, fault):
        self.inner, self.fault, self.first = harness.build_session(cell), fault, None

    def round(self, grads):
        if self.fault == "unchanged":
            # every round hands back the state of the first
            self.first = self.first or self.inner.round(grads)
            return self.first
        if self.fault == "half_batch":
            return self.inner.round(grads[: len(grads) // 2])
        res = self.inner.round(grads)
        res.avg_flat[len(res.avg_flat) // 2] = np.nextafter(
            res.avg_flat[len(res.avg_flat) // 2], np.float32(np.inf))
        return res


@pytest.mark.parametrize("name", ["resnet18-n20-m8.identity", "resnet18-n20-m8.topk"])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered_answer"])
def test_faults_are_not_correct(name, fault):
    res = run(tiny(name), session_factory=lambda cell: _Broken(cell, fault))
    assert not res["correct"] and res["failed"] >= 1


def test_answer_altered_inside_the_engine_is_not_correct(monkeypatch):
    from repro.core import agg_engine

    orig = agg_engine._evaluate_nodes

    def evaluate(nodes, *a, **k):
        orig(nodes, *a, **k)
        for nd in nodes:
            if nd.out is not None and nd.out.size:
                nd.out[0] = np.nextafter(nd.out[0], np.float32(np.inf))
                break

    monkeypatch.setattr(agg_engine, "_evaluate_nodes", evaluate)
    res = run(tiny("resnet18-n20-m8.identity"))
    assert not res["correct"] and res["checks"]["max_ulp_gap"]["value"] == 1

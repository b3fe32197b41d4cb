"""The program's tracing spans (``repro.tracing``).

A span records only under a ``jax.profiler`` trace, carries its counts as
the event's stats, and reaches no value: a traced round is bit-identical
to an untraced one. The Pallas fold runs in interpret mode here
(``REPRO_AGG_PALLAS=1``): with one worker, through the staging buffers
and the two window lanes the chip path runs, in windows of one tile; with
two, on the fold pool.
"""
from __future__ import annotations

import glob

import jax
import numpy as np
import pytest

from repro.api import FederatedSession, SessionConfig
from repro.kernels import ops
from repro.tracing import PREFIX, span

N, ELEMS, M, WORKERS = 3, 10_000, 2, 2
TILE = 32 * ops.LANES

#: child span -> the span it runs inside
PARENT = {
    "round.program": "session.round",
    "codec.encode": "round.program",
    "round.upload": "session.round",
    "round.phases": "session.round",
    "engine.end_round": "session.round",
    "fold.stream": "engine.end_round",
    "fold.chip": "fold.stream",
    "fold.window": "fold.chip",
    "fold.fill": "fold.window",
    "fold.h2d": "fold.window",
    "fold.kernel": "fold.window",
    "fold.d2h": "fold.window",
    "fold.divide": "fold.window",
    "round.readback": "session.round",
}
FOLD_PARTS = ["fold.fill", "fold.h2d", "fold.kernel", "fold.d2h", "fold.divide"]


def test_span_yields_none_without_a_profiler():
    with span("fold.h2d", bytes=123) as got:
        assert got is None
    with span("session.round") as got:
        assert got is None


def _grads(seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(ELEMS, dtype=np.float32) for _ in range(N)]


def _session(codec: str, workers: int) -> FederatedSession:
    return FederatedSession(SessionConfig(n_shards=M, codec=codec, workers=workers,
                                          track_codec_error=False))


def _program_events(log_dir: str) -> list:
    """(name without the prefix, start, end, stats, thread) of each span."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for k, ln in enumerate(plane.lines):
            out.extend((e.name[len(PREFIX):], e.start_ns, e.end_ns, dict(e.stats),
                        (plane.name, k))
                       for e in ln.events if e.name.startswith(PREFIX))
    return out


@pytest.fixture(scope="module", params=[
    pytest.param(("identity", WORKERS), id="identity"),
    pytest.param(("topk", WORKERS), id="topk"),
    pytest.param(("identity", 1), id="identity-staged"),
    pytest.param(("topk", 1), id="topk-staged"),
])
def rounds(request, tmp_path_factory):
    """Round 1 of two sessions on the same gradients, untraced and traced,
    and the fold windows of the round."""
    codec, workers = request.param
    grads = [_grads(0), _grads(1)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_AGG_PALLAS", "1")
        if workers == 1:
            mp.setattr(ops, "STAGING_BYTES", 4 * N * TILE)
        windows = ops.fold_windows(ELEMS, N, None, workers)
        plain, traced = _session(codec, workers), _session(codec, workers)
        plain.round(grads[0])
        traced.round(grads[0])
        want = plain.round(grads[1])
        log_dir = str(tmp_path_factory.mktemp(f"trace-{codec}"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        with jax.profiler.trace(log_dir, profiler_options=opts):
            got = traced.round(grads[1])
    return plain, want, traced, got, (workers, windows), _program_events(log_dir)


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_traced_round_emits_every_span_nested(rounds):
    *_, events = rounds
    assert {e[0] for e in events} == {"session.round", *PARENT}
    for name, parent in PARENT.items():
        for child in _named(events, name):
            same_thread = name.startswith("fold.") and name != "fold.window"
            assert any(_inside(child, p) and (not same_thread or p[4] == child[4])
                       for p in _named(events, parent)), (name, parent)
    for w in _named(events, "fold.window"):
        parts = [e for e in events if e[0] in FOLD_PARTS and e[4] == w[4]
                 and _inside(e, w)]
        assert [e[0] for e in sorted(parts, key=lambda e: e[1])] == FOLD_PARTS


def test_span_counts_match_the_shapes(rounds):
    *_, (_, windows), events = rounds
    (sess,) = _named(events, "session.round")
    assert sess[3] == {"rnd": 1, "n": N}
    encodes = _named(events, "codec.encode")
    assert len(encodes) == N * M and all(e[3] == {"elems": ELEMS // M} for e in encodes)
    assert _named(events, "round.upload")[0][3] == {"puts": N * M}
    assert _named(events, "round.phases")[0][3] == {"invocations": M}
    assert _named(events, "engine.end_round")[0][3] == {"kernel_folds": M}
    assert _named(events, "round.readback")[0][3] == {"bytes": ELEMS * 4,
                                                      "copied": 0}
    cols = [-(-(b - a) // TILE) * TILE for a, b in windows]
    got = sorted((e[3]["index"], e[3]["n"], e[3]["cols"])
                 for e in _named(events, "fold.window"))
    assert got == [(k, N, c) for k, c in enumerate(cols)]
    for name, per_window in [("fold.fill", N * 4), ("fold.h2d", N * 4), ("fold.d2h", 4)]:
        assert sorted(e[3]["bytes"] for e in _named(events, name)) \
            == sorted(c * per_window for c in cols)
    assert all(e[3]["chip"] == 0 for e in events if e[0] in ["fold.window", *FOLD_PARTS])
    assert all(e[3] == {"chip": 0} for e in events if e[0] in ("fold.kernel",
                                                               "fold.divide"))
    assert _named(events, "round.program")[0][3] == {}


def test_fold_stream_counts_its_windows_and_buffers(rounds):
    """One ``fold.stream`` a round: its windows, and on the staged path
    (one worker) staging buffers warm from the rounds before, so none is
    allocated; the fold pool's windows each take fresh memory."""
    *_, (workers, windows), events = rounds
    (stream,) = _named(events, "fold.stream")
    counts = stream[3]
    assert set(counts) == {"windows", "chips", "max_chip_bytes", "min_chip_bytes",
                           "staging_bytes", "allocs", "overlapped"}
    assert counts["windows"] == len(windows) == len(_named(events, "fold.window"))
    assert 0 <= counts["overlapped"] <= len(windows) - 1
    cols = max(-(-(b - a) // TILE) * TILE for a, b in windows)
    in_bytes = 4 * N * sum(-(-(b - a) // TILE) * TILE for a, b in windows)
    assert counts["chips"] == 1
    assert counts["max_chip_bytes"] == counts["min_chip_bytes"] == in_bytes
    (chip,) = _named(events, "fold.chip")
    assert chip[3] == {"chip": 0, "windows": len(windows), "bytes": in_bytes,
                       "overlapped": counts["overlapped"]}
    if workers == 1:
        assert len(windows) == -(-ELEMS // TILE)
        assert counts["allocs"] == 0
        assert counts["staging_bytes"] >= 4 * N * cols
    else:
        assert counts["allocs"] == len(windows) and counts["staging_bytes"] == 0


def test_traced_round_is_bit_identical(rounds):
    plain, want, traced, got, _, _ = rounds
    assert np.array_equal(got.avg_flat, want.avg_flat)
    assert got.kernel_folds == want.kernel_folds == M
    assert (got.wall_clock_s, got.phases_s) == (want.wall_clock_s, want.phases_s)
    assert (got.puts, got.gets) == (want.puts, want.gets)
    assert got.records == want.records
    assert traced.total_cost() == plain.total_cost()
    assert traced.summary() == plain.summary()


@pytest.mark.parametrize("kernel, copied", [(True, 0), (False, ELEMS * 4)],
                         ids=["kernel", "numpy"])
def test_readback_counts_the_bytes_collect_copied(monkeypatch, tmp_path,
                                                  kernel, copied):
    """``round.readback`` carries ``copied``: none where the fold divided
    into the round's result buffer, the whole gradient where the numpy
    evaluator's separate shards are copied into a new one."""
    monkeypatch.setenv("REPRO_AGG_PALLAS", "1" if kernel else "0")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        got = _session("identity", 1).round(_grads(0))
    assert got.kernel_folds == (M if kernel else 0)
    (readback,) = _named(_program_events(str(tmp_path)), "round.readback")
    assert readback[3] == {"bytes": ELEMS * 4, "copied": copied}


def test_four_chip_fold_spans_count_each_chip():
    """On four devices (a subprocess with four host CPU devices) each call
    has one ``fold.chip`` span per chip, inside ``fold.stream``, with its
    windows, input bytes and overlapped windows; every window part names
    its chip, and ``fold.stream`` the chips and their largest and smallest
    input."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(f"""
        import glob, json, os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from jax.profiler import ProfileData
        from repro.kernels import ops
        n, tile = {N}, {TILE}
        ops.STAGING_BYTES = 4 * n * tile
        stacks = [np.ones((n, l), np.float32) for l in (7 * tile + 5, 2 * tile)]
        ops.fedavg_multi(stacks, workers=1, devices=jax.local_devices())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d, profiler_options=opts):
                ops.fedavg_multi(stacks, workers=1, devices=jax.local_devices())
            path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
            ev = [(e.name[len("repro."):], e.start_ns, e.end_ns, dict(e.stats))
                  for p in ProfileData.from_file(path).planes for ln in p.lines
                  for e in ln.events if e.name.startswith("repro.")]
        print(json.dumps(ev))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_AGG_PALLAS="1")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    events = json.loads(out.stdout.strip().splitlines()[-1])
    tiles = [3, 3, 2, 2]                       # 10 tiles over four chips
    (stream,) = _named(events, "fold.stream")
    assert stream[3]["chips"] == 4 and stream[3]["windows"] == 10
    assert stream[3]["max_chip_bytes"] == 4 * N * 3 * TILE
    assert stream[3]["min_chip_bytes"] == 4 * N * 2 * TILE
    assert stream[3]["allocs"] == 0
    chips = sorted(_named(events, "fold.chip"), key=lambda e: e[3]["chip"])
    assert [e[3]["chip"] for e in chips] == [0, 1, 2, 3]
    assert [e[3]["windows"] for e in chips] == tiles
    assert [e[3]["bytes"] for e in chips] == [4 * N * t * TILE for t in tiles]
    assert sum(e[3]["overlapped"] for e in chips) == stream[3]["overlapped"]
    assert all(0 <= e[3]["overlapped"] < e[3]["windows"] for e in chips)
    assert all(_inside(e, stream) for e in chips)
    for name in ["fold.window", *FOLD_PARTS]:
        spans = _named(events, name)
        assert sorted(e[3]["chip"] for e in spans) \
            == [c for c, t in enumerate(tiles) for _ in range(t)]
        assert all(_inside(e, chips[e[3]["chip"]]) for e in spans)

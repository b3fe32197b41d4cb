"""Aggregation execution engine: the batched backend must be
indistinguishable from the streaming reference — bit-identical ``avg_flat``
and byte-identical platform accounting (the paper's
invariance-by-construction property, enforced)."""
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core.agg_engine import (
    BatchedBackend,
    LazyAverage,
    StreamingBackend,
    _evaluate_nodes,
    get_backend,
)
from repro.core.sharding import make_plan, shard, shard_views
from repro.serverless import FaultPlan, LambdaRuntime
from repro.store import ObjectStore


def _grads(n, size, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(size).astype(np.float32) for _ in range(n)]


def _run(topo, engine, n=20, size=5_003, seed=0, faults=None, **kw):
    grads = _grads(n, size, seed)
    store, rt = ObjectStore(), LambdaRuntime(faults=faults)
    r = agg.aggregate_round(topo, grads, rnd=0, store=store, runtime=rt,
                            engine=engine, **kw)
    return r, rt, store


def _assert_identical(a, b):
    """a = streaming result, b = batched result."""
    assert np.array_equal(a[0].avg_flat, b[0].avg_flat), \
        "batched avg_flat must be bit-identical to the streaming reference"
    ra, rb = a[0], b[0]
    assert ra.puts == rb.puts
    assert ra.gets == rb.gets
    assert ra.wall_clock_s == rb.wall_clock_s
    assert ra.phases_s == rb.phases_s
    assert ra.memory_mb == rb.memory_mb
    assert ra.peak_memory_mb == rb.peak_memory_mb
    # per-invocation records, field by field
    assert len(a[1].records) == len(b[1].records)
    for x, y in zip(a[1].records, b[1].records):
        assert (x.fn_name, x.attempt, x.failed, x.speculative) == \
               (y.fn_name, y.attempt, y.failed, y.speculative)
        assert x.billed_gb_s == y.billed_gb_s
        assert x.duration_s == y.duration_s
        assert x.peak_memory_mb == y.peak_memory_mb
        assert (x.read_bytes, x.write_bytes, x.compute_bytes) == \
               (y.read_bytes, y.write_bytes, y.compute_bytes)


# ---------------------------------------------------------------------------
# Bit-identity + accounting identity across topologies / partitions / N
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 20, 27])
@pytest.mark.parametrize("topo,kw", [
    ("gradssharding", {"n_shards": 1}),
    ("gradssharding", {"n_shards": 4}),
    ("gradssharding", {"n_shards": 16}),
    ("lambda_fl", {}),
    ("lifl", {}),
    ("lifl", {"colocated": True}),
])
def test_batched_matches_streaming(topo, kw, n):
    a = _run(topo, "streaming", n=n, **kw)
    b = _run(topo, "batched", n=n, **kw)
    _assert_identical(a, b)


@pytest.mark.parametrize("partition,sizes", [
    ("uniform", None),
    ("layer_contiguous", [1_000, 3, 4_000]),
    ("balanced", [1_000, 3, 4_000]),
    ("balanced", [2_500, 2_500, 3]),     # M > #tensors -> empty shards
])
def test_batched_matches_streaming_partitions(partition, sizes):
    kw = {"n_shards": 8, "partition": partition, "tensor_sizes": sizes}
    a = _run("gradssharding", "streaming", **kw)
    b = _run("gradssharding", "batched", **kw)
    _assert_identical(a, b)


def test_batched_store_contents_materialized():
    """After a batched round every stored object is a real array, equal
    bit-for-bit to what the streaming round stored. (Under a lossy wire
    codec env, client uploads are WirePayloads by design — in *both*
    engines — and only aggregator outputs are arrays.)"""
    from repro.core.wire_codec import WirePayload
    a = _run("lifl", "streaming")
    b = _run("lifl", "batched")
    assert a[2].list() == b[2].list()
    for key in a[2].list():
        va, vb = a[2].peek(key), b[2].peek(key)
        if isinstance(va, WirePayload):
            assert isinstance(vb, WirePayload), key
            for part in va.parts:
                assert np.array_equal(va.parts[part], vb.parts[part]), key
            continue
        assert isinstance(vb, np.ndarray), key
        assert np.array_equal(va, vb), key


# ---------------------------------------------------------------------------
# Fault tolerance parity
# ---------------------------------------------------------------------------

def test_batched_retry_and_straggler_identical():
    faults = lambda: FaultPlan(  # noqa: E731 — fresh plan per run
        fail={("r0-shard1", 0), ("r0-shard1", 1)},
        slow={("r0-shard0", 0): 25.0})
    a = _run("gradssharding", "streaming", n=8, size=2_048,
             faults=faults(), n_shards=4, straggler_threshold_s=1.0)
    b = _run("gradssharding", "batched", n=8, size=2_048,
             faults=faults(), n_shards=4, straggler_threshold_s=1.0)
    _assert_identical(a, b)
    assert any(r.speculative for r in b[1].records)
    assert any(r.failed for r in b[1].records)


def test_batched_all_attempts_fail_raises():
    faults = FaultPlan(fail={("r0-shard0", i) for i in range(5)})
    with pytest.raises(RuntimeError, match="attempts failed"):
        _run("gradssharding", "batched", n=4, size=256, faults=faults,
             n_shards=2)


# ---------------------------------------------------------------------------
# Engine selection knob
# ---------------------------------------------------------------------------

def test_engine_knob(monkeypatch):
    assert get_backend("streaming").name == "streaming"
    assert get_backend("batched").name == "batched"
    backend = BatchedBackend()
    assert get_backend(backend) is backend
    monkeypatch.delenv("REPRO_AGG_ENGINE", raising=False)
    assert get_backend(None).name == "batched"          # default
    monkeypatch.setenv("REPRO_AGG_ENGINE", "streaming")
    assert get_backend(None).name == "streaming"
    assert get_backend("auto").name == "streaming"
    with pytest.raises(ValueError, match="unknown aggregation engine"):
        get_backend("warp-drive")


def test_result_reports_engine():
    assert _run("gradssharding", "streaming", n=4, size=512,
                n_shards=2)[0].engine == "streaming"
    assert _run("gradssharding", "batched", n=4, size=512,
                n_shards=2)[0].engine == "batched"


def test_backends_are_fresh_per_round():
    assert get_backend("batched") is not get_backend("batched")


# ---------------------------------------------------------------------------
# Zero-copy shard views
# ---------------------------------------------------------------------------

def test_shard_view_zero_copy_uniform():
    flat = np.arange(1_000, dtype=np.float32)
    plan = make_plan("uniform", 1_000, 4)
    views = shard_views(flat, plan)
    eager = shard(flat, plan)
    for v, e in zip(views, eager):
        assert v.nbytes == e.nbytes
        mat = v.materialize()
        assert np.array_equal(mat, e)
        assert mat.base is flat or mat is flat    # a view, not a copy


def test_shard_view_chunk_reads_balanced():
    flat = np.arange(8_003, dtype=np.float32)
    plan = make_plan("balanced", 8_003, 4, [3_000, 5, 4_998])
    views = shard_views(flat, plan)
    eager = shard(flat, plan)
    for v, e in zip(views, eager):
        assert v.size == e.size
        got = np.concatenate([v.read(s, min(s + 37, v.size))
                              for s in range(0, v.size, 37)]) \
            if v.size else np.empty(0, np.float32)
        assert np.array_equal(got, e)
        assert np.array_equal(v.materialize(), e)


# ---------------------------------------------------------------------------
# Evaluator internals
# ---------------------------------------------------------------------------

def test_lazy_average_standalone_materialize():
    xs = _grads(6, 10_000)
    leaf1 = LazyAverage(xs[:3], [1.0, 1.0, 1.0])
    leaf2 = LazyAverage(xs[3:], [1.0, 1.0, 1.0])
    root = LazyAverage([leaf1, leaf2], [3.0, 3.0])
    got = root.materialize()                 # pulls ancestors transitively
    acc = xs[0].astype(np.float64)
    for x in xs[1:3]:
        acc += x.astype(np.float64)
    p1 = (acc / 3.0).astype(np.float32)
    acc = xs[3].astype(np.float64)
    for x in xs[4:]:
        acc += x.astype(np.float64)
    p2 = (acc / 3.0).astype(np.float32)
    ref = ((p1.astype(np.float64) * 3.0 + p2.astype(np.float64) * 3.0)
           / 6.0).astype(np.float32)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("chunk", [64, 1_024, 1 << 18])
def test_evaluator_chunk_size_invariant(chunk):
    xs = _grads(7, 5_003, seed=3)
    ref_node = LazyAverage(list(xs), None)
    _evaluate_nodes([ref_node], chunk=1 << 18)
    node = LazyAverage(list(xs), None)
    _evaluate_nodes([node], chunk=chunk)
    assert np.array_equal(node.out, ref_node.out)


def test_streaming_ops_match_seed_semantics():
    """The streaming backend is the seed implementation: left-fold f32 for
    unweighted, f64 scaled left-fold for weighted."""
    be = StreamingBackend()
    xs = _grads(4, 257, seed=9)
    acc = be.init_acc(xs[0], None)
    for i, x in enumerate(xs[1:], 1):
        acc = be.accumulate(acc, x, i, None)
    out = be.finalize(acc, None, len(xs))
    ref = xs[0].astype(np.float32).copy()
    for x in xs[1:]:
        ref += x
    assert np.array_equal(out, (ref / 4.0).astype(np.float32))


# ---------------------------------------------------------------------------
# Pallas path (interpret mode on CPU hosts): same accumulation order, the
# kernel returns sums and the divide is the evaluator's f32 op on the host
# — hence array_equal, not allclose
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_batched_pallas_backend_close():
    backend = BatchedBackend(use_pallas=True)
    b = _run("gradssharding", backend, n=5, size=2_048, n_shards=2)
    a = _run("gradssharding", "streaming", n=5, size=2_048, n_shards=2)
    assert np.array_equal(b[0].avg_flat, a[0].avg_flat)
    assert a[0].puts == b[0].puts and a[0].gets == b[0].gets
    assert (b[0].kernel_folds, a[0].kernel_folds) == (2, 0)


@pytest.mark.slow
def test_fedavg_multi_matches_per_shard_calls():
    from repro.kernels import ops
    rng = np.random.default_rng(11)
    stacks = [rng.standard_normal((6, l)).astype(np.float32)
              for l in (300, 1_024, 7)]
    multi = ops.fedavg_multi(stacks)
    for stack, got in zip(stacks, multi):
        single = ops.fedavg_shards(np.asarray(stack))
        assert np.array_equal(got, single)


def _left_fold_mean(stack):
    acc = stack[0].copy()
    for row in stack[1:]:
        acc += row
    return acc / np.float32(len(stack))


def _fold_stream_counts(call) -> list:
    """Run ``call()`` under a profiler trace; the stats of each
    ``repro.fold.stream`` span it recorded, in order."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as log_dir:
        with jax.profiler.trace(log_dir, profiler_options=opts):
            call()
        path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
        events = [e for plane in ProfileData.from_file(path).planes
                  for ln in plane.lines for e in ln.events
                  if e.name == "repro.fold.stream"]
        return [dict(e.stats) for e in sorted(events, key=lambda e: e.start_ns)]


@pytest.mark.parametrize(
    "bound, tiles_per_launch",
    [pytest.param("budget", t, id=str(t)) for t in (1, 2, 5)]
    + [pytest.param("staging", t, id=f"staging-{t}") for t in (1, 2, 5)])
def test_fedavg_multi_byte_bounded_windows(monkeypatch, bound,
                                           tiles_per_launch):
    """A device budget or a staging size of a few kernel tiles cuts the
    round's columns into many tile-aligned launches — across shard
    boundaries — and every cut stays bit-identical to the streaming f32
    left-fold + one f32 divide. The budget holds two launches in flight;
    a staging buffer holds one window's (N, cols) input."""
    from repro.kernels import ops
    n, tile = 4, 32 * 128
    if bound == "budget":
        budget = 2 * 4 * (n + 1) * tile * tiles_per_launch
        monkeypatch.setattr(ops, "fold_budget_bytes",
                            lambda device=None: budget)
    else:
        monkeypatch.setattr(ops, "STAGING_BYTES",
                            4 * n * tile * tiles_per_launch)
    rng = np.random.default_rng(12)
    stacks = [rng.standard_normal((n, l)).astype(np.float32)
              for l in (5_000, 4_096, 3, 9_001)]
    windows = ops.fold_windows(sum(s.shape[1] for s in stacks), n,
                               ops.fold_budget_bytes())
    assert len(windows) >= -(-5 // tiles_per_launch)
    assert all(a % tile == 0 and b - a <= tile * tiles_per_launch
               for a, b in windows)
    assert all(4 * n * -(-(b - a) // tile) * tile <= ops.STAGING_BYTES
               for a, b in windows)
    for stack, got in zip(stacks, ops.fedavg_multi(stacks, workers=1)):
        assert np.array_equal(got, _left_fold_mean(stack))


@pytest.mark.parametrize("first, second", [
    ((6, (9_000, 5_000)), (3, (4_100, 7))),
    ((3, (4_100, 7)), (6, (9_000, 5_000))),
], ids=["shrink", "grow"])
def test_fedavg_multi_reuses_staging_without_stale_data(monkeypatch, first,
                                                        second):
    """Two calls in a row stream through the same two staging buffers, the
    second with another N, other lengths and other data: no row or pad
    column left by the first reaches the second's means, and the second
    allocates no staging buffer."""
    from repro.kernels import ops
    tile = 32 * 128
    monkeypatch.setattr(ops, "STAGING_BYTES", 4 * 6 * tile * 2)
    rng = np.random.default_rng(13)
    calls = [[rng.standard_normal((n, l)).astype(np.float32) for l in lens]
             for n, lens in (first, second)]
    got = []
    counts = _fold_stream_counts(
        lambda: got.extend(ops.fedavg_multi(c, workers=1) for c in calls))
    for stacks, means in zip(calls, got):
        for stack, mean in zip(stacks, means):
            assert np.array_equal(mean, _left_fold_mean(stack))
    assert [c["windows"] for c in counts] == [
        len(ops.fold_windows(sum(s.shape[1] for s in stacks), len(stacks[0]),
                             None)) for stacks in calls]
    assert counts[0]["allocs"] <= 2 and counts[1]["allocs"] == 0
    assert all(c["staging_bytes"] >= ops.STAGING_BYTES for c in counts)


def test_fedavg_multi_fills_the_next_window_while_one_is_in_flight(
        monkeypatch):
    """With fills and launches of 0.1 s or more, and the kernel compiled,
    every window's fill after the first runs while an earlier window is
    on its way or folding."""
    import time

    from repro.kernels import ops
    n, tile = 3, 32 * 128
    monkeypatch.setattr(ops, "STAGING_BYTES", 4 * n * tile)
    fold_sum = ops._fold_sum

    def slow_fold_sum(*args):
        time.sleep(0.1)
        return fold_sum(*args)

    monkeypatch.setattr(ops, "_fold_sum", slow_fold_sum)
    stack = np.random.default_rng(14).standard_normal(
        (n, 4 * tile)).astype(np.float32)

    def slow_read(row, s, e):
        time.sleep(0.05)
        return row[s:e]

    ops.fedavg_multi([stack], workers=1)          # compile the kernel
    got = []
    (counts,) = _fold_stream_counts(
        lambda: got.extend(ops.fedavg_multi([stack], workers=1,
                                            read=slow_read)))
    assert np.array_equal(got[0], _left_fold_mean(stack))
    assert counts["windows"] == 4
    assert counts["overlapped"] == 3


def _assert_views_of_one_buffer(means, lengths) -> np.ndarray:
    """The means are consecutive views, in order, of one f32 buffer of
    sum(lengths) elements; returns that buffer."""
    buf = means[0].base
    assert isinstance(buf, np.ndarray) and buf.dtype == np.float32
    assert buf.shape == (sum(lengths),) and buf.flags.writeable
    addr = buf.__array_interface__["data"][0]
    for off, l, mean in zip(np.cumsum([0] + lengths), lengths, means):
        assert mean.base is buf and mean.shape == (l,)
        assert mean.__array_interface__["data"][0] == addr + 4 * off
    return buf


@pytest.mark.parametrize("workers", [1, 2], ids=["staged", "fold-pool"])
def test_fedavg_multi_divides_into_views_of_one_buffer(monkeypatch, workers):
    """One call's means are consecutive views of one new buffer, in stack
    order, across many windows; they are bit-identical to the numpy
    evaluator, and the buffer shares no memory with the inputs, the
    staging buffers or an earlier call's buffer."""
    from repro.kernels import ops
    n, tile = 4, 32 * 128
    monkeypatch.setattr(ops, "STAGING_BYTES", 4 * n * tile)
    rng = np.random.default_rng(15)
    lengths = [5_000, 3, 9_001, 4_096]
    stacks = [rng.standard_normal((n, l)).astype(np.float32) for l in lengths]
    means = ops.fedavg_multi(stacks, workers=workers)
    again = ops.fedavg_multi(stacks, workers=workers)
    buf = _assert_views_of_one_buffer(means, lengths)
    nodes = [LazyAverage(list(stack), None) for stack in stacks]
    _evaluate_nodes(nodes)
    for mean, nd, twice in zip(means, nodes, again):
        assert np.array_equal(mean, nd.out) and np.array_equal(twice, nd.out)
    for other in [*stacks, *ops._staging.values(), again[0].base]:
        assert not np.shares_memory(buf, other)


def test_fold_windows_refuses_a_tile_over_budget():
    from repro.kernels import ops
    with pytest.raises(ValueError, match="device budget"):
        ops.fold_windows(10_000, 20, 1_000)

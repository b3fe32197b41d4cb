"""FederatedSession facade: config plumbing, multi-round pipelining,
per-client local-compute modeling, the colocated pipelined cost entry, and
bounded-memory long sessions (``keep_records=False``)."""
import numpy as np
import pytest

from repro.api import FederatedSession, SessionConfig
from repro.core import cost_model as cm
from repro.core.cost_model import UploadModel
from repro.serverless import FaultPlan, LambdaRuntime
from repro.store import ObjectStore

MB = 1024 * 1024


def _grads(n=8, size=4_096, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(size).astype(np.float32) for _ in range(n)]


# ---------------------------------------------------------------------------
# Facade basics
# ---------------------------------------------------------------------------

def test_session_owns_substrate_and_numbers_rounds():
    session = FederatedSession(SessionConfig(topology="gradssharding",
                                             n_shards=4))
    assert isinstance(session.store, ObjectStore)
    assert isinstance(session.runtime, LambdaRuntime)
    r0 = session.round(_grads())
    r1 = session.round(_grads(seed=1))
    assert session.rounds_run == 2
    # auto-numbered rounds land in disjoint keyspaces
    assert r0.avg_flat.shape == r1.avg_flat.shape
    assert session.store.exists("round00000/avg/shard0000")
    assert session.store.exists("round00001/avg/shard0000")
    summary = session.summary()
    assert summary["rounds"] == 2 and summary["total_cost"] > 0


def test_session_kwarg_overrides_and_eager_validation():
    session = FederatedSession(topology="lifl", colocated=True,
                               engine="streaming")
    assert session.config.topology == "lifl"
    with pytest.raises(ValueError, match="unknown topology"):
        FederatedSession(topology="nope")
    with pytest.raises(ValueError, match="unknown aggregation engine"):
        FederatedSession(engine="warp").round(_grads(2, 64))


def test_session_injected_runtime_and_faults():
    faults = FaultPlan(fail={("r0-shard1", 0)})
    session = FederatedSession(SessionConfig(n_shards=4, codec="identity"),
                               faults=faults)
    grads = _grads()
    r = session.round(grads)
    acc = grads[0].copy()
    for g in grads[1:]:
        acc += g
    assert np.array_equal(r.avg_flat, acc / len(grads))
    assert any(rec.failed for rec in session.runtime.records)


def test_session_rejects_injected_runtime_plus_runtime_config():
    rt = LambdaRuntime()
    with pytest.raises(ValueError, match="injected runtime"):
        FederatedSession(SessionConfig(warm_pool_size=2), runtime=rt)
    with pytest.raises(ValueError, match="faults"):
        FederatedSession(runtime=rt, faults=FaultPlan())
    FederatedSession(runtime=rt)                       # alone: fine


def test_session_handles_per_round_client_sampling():
    """A resized client cohort must not inherit the previous cohort's
    per-client ready times (it would crash on growth, misassign on
    shrink) — the session restarts the cohort from the runtime cursor."""
    up = UploadModel(mbps=16.0, download_mbps=32.0, jitter_s=2.0, seed=3)
    session = FederatedSession(SessionConfig(n_shards=4,
                                             schedule="pipelined",
                                             upload=up))
    r20 = session.round(_grads(20, 1_024, seed=0))
    r30 = session.round(_grads(30, 1_024, seed=1))      # cohort grows
    r5 = session.round(_grads(5, 1_024, seed=2))        # cohort shrinks
    assert len(r30.client_done_s) == 30 and len(r5.client_done_s) == 5
    # resized rounds start at the cursor, not at stale per-client times
    assert r30.round_start_s >= r20.round_end_s
    # same-size rounds still pipeline
    r5b = session.round(_grads(5, 1_024, seed=3))
    assert r5b.round_start_s == pytest.approx(min(r5.client_done_s))


def test_result_costs_priced_with_session_limits():
    import dataclasses
    from repro.config import DEFAULT_LIMITS
    pricey = dataclasses.replace(DEFAULT_LIMITS,
                                 gb_s_price=2 * DEFAULT_LIMITS.gb_s_price)
    session = FederatedSession(SessionConfig(n_shards=2, limits=pricey))
    grads = _grads(4, 1_024)
    res = session.round(grads)
    assert res.limits is pricey
    assert res.lambda_cost == pytest.approx(session.lambda_cost())
    assert res.s3_cost() == pytest.approx(session.s3_cost())
    assert res.total_cost() == pytest.approx(session.total_cost())
    # default-limits sessions are unchanged
    default = FederatedSession(SessionConfig(n_shards=2)).round(grads)
    assert default.limits is DEFAULT_LIMITS


def test_session_run_is_lazy_iterator():
    session = FederatedSession(SessionConfig(n_shards=2))
    seen = []
    it = session.run(lambda rnd: _grads(4, 256, seed=rnd), rounds=3)
    assert session.rounds_run == 0            # nothing ran yet
    for r in it:
        seen.append(r)
    assert len(seen) == 3 and session.rounds_run == 3


def test_session_matches_federated_train_loop():
    from repro.launch.train import federated_train_loop
    up = UploadModel(mbps=16.0, download_mbps=32.0, jitter_s=2.0, seed=3)
    grads_by_round = [_grads(6, 2_048, seed=100 + r) for r in range(3)]
    out = federated_train_loop(lambda rnd: grads_by_round[rnd], rounds=3,
                               n_shards=4, schedule="pipelined", upload=up)
    session = FederatedSession(SessionConfig(
        n_shards=4, schedule="pipelined", upload=up))
    results = [session.round(g) for g in grads_by_round]
    for a, b in zip(out["results"], results):
        assert np.array_equal(a.avg_flat, b.avg_flat)
        assert a.round_start_s == b.round_start_s
        assert a.round_end_s == b.round_end_s
    assert out["session_wall_s"] == pytest.approx(session.session_wall_s)
    assert out["sum_round_walls_s"] == pytest.approx(
        session.sum_round_walls_s)


# ---------------------------------------------------------------------------
# Per-client local-compute time (UploadModel.compute_s)
# ---------------------------------------------------------------------------

def test_session_config_local_compute_override():
    cfg = SessionConfig(local_compute_s=5.0)
    assert cfg.resolved_upload().compute_s == 5.0
    cfg2 = SessionConfig(upload=UploadModel(mbps=16.0), local_compute_s=2.0)
    up = cfg2.resolved_upload()
    assert up.mbps == 16.0 and up.compute_s == 2.0
    assert SessionConfig().resolved_upload() is None
    # the override reaches the round: wall grows by the serialized compute
    grads = _grads(4, 1_024)
    plain = FederatedSession(SessionConfig(n_shards=2)).round(grads)
    delayed = FederatedSession(SessionConfig(n_shards=2,
                                             local_compute_s=5.0)
                               ).round(grads)
    assert delayed.wall_clock_s == pytest.approx(plain.wall_clock_s + 5.0)


def test_compute_plan_deterministic_and_separate_stream():
    up = UploadModel(jitter_s=3.0, compute_s=5.0, compute_jitter=2.0,
                     seed=9)
    c1, c2 = up.compute_plan(8, rnd=1), up.compute_plan(8, rnd=1)
    assert np.array_equal(c1, c2)
    assert (c1 >= 5.0).all() and (c1 < 7.0).all()
    # adding compute never perturbs the upload draws
    base = UploadModel(jitter_s=3.0, seed=9)
    s1, m1 = base.plan(8, rnd=1)
    s2, m2 = up.plan(8, rnd=1)
    assert np.array_equal(s1, s2) and np.array_equal(m1, m2)
    assert np.array_equal(UploadModel().compute_plan(4), np.zeros(4))


@pytest.mark.parametrize("topology,m", [("gradssharding", 8),
                                        ("lambda_fl", 1), ("lifl", 1)])
@pytest.mark.parametrize("schedule", ["barrier", "pipelined"])
def test_compute_time_cost_model_parity(topology, m, schedule):
    """The analytical model and the event sim see identical per-client
    train-then-upload plans."""
    n, elems = 20, 8_192
    up = UploadModel(mbps=16.0, jitter_s=3.0, rate_jitter=0.5,
                     compute_s=5.0, compute_jitter=2.0, seed=7)
    sim = FederatedSession(topology=topology, n_shards=m,
                           schedule=schedule, upload=up).round(_grads(n,
                                                                      elems))
    fn = cm.pipelined_round_cost if schedule == "pipelined" \
        else cm.barrier_round_cost
    model = fn(topology, elems * 4, n, m, upload=up)
    assert model.wall_clock_s == pytest.approx(sim.wall_clock_s, rel=1e-9)


def test_compute_overlaps_readback_in_pipelined_sessions():
    """ROADMAP item: with local-compute time modeled, a pipelined session
    overlaps round r+1 training with round r read-back; a barrier session
    serializes them."""
    up = UploadModel(mbps=16.0, download_mbps=8.0, jitter_s=2.0,
                     rate_jitter=1.0, compute_s=4.0, seed=3)
    walls = {}
    for sched in ("barrier", "pipelined"):
        session = FederatedSession(SessionConfig(n_shards=4, schedule=sched,
                                                 upload=up))
        for rnd in range(3):
            session.round(_grads(6, 32_768, seed=rnd))
        walls[sched] = session.session_wall_s
    assert walls["pipelined"] < walls["barrier"]
    # and the overlap win grows vs the no-compute model (more to hide)
    no_compute = UploadModel(mbps=16.0, download_mbps=8.0, jitter_s=2.0,
                             rate_jitter=1.0, seed=3)
    session = FederatedSession(SessionConfig(n_shards=4,
                                             schedule="pipelined",
                                             upload=no_compute))
    for rnd in range(3):
        session.round(_grads(6, 32_768, seed=rnd))
    assert walls["pipelined"] > session.session_wall_s  # compute still costs


# ---------------------------------------------------------------------------
# Colocated LIFL pipelined cost entry (ROADMAP item)
# ---------------------------------------------------------------------------

def test_colocated_pipelined_cost_matches_simulation():
    n, elems = 20, 65_536
    up = UploadModel(mbps=16.0, jitter_s=3.0, rate_jitter=0.5, seed=11)
    sim = FederatedSession(topology="lifl", schedule="pipelined",
                           upload=up, colocated=True).round(_grads(n, elems))
    model = cm.pipelined_round_cost("lifl", elems * 4, n, upload=up,
                                    colocated=True)
    assert model.wall_clock_s == pytest.approx(sim.wall_clock_s, rel=1e-9)
    assert (model.ops.puts, model.ops.gets) == (sim.puts, sim.gets)
    # shared-memory hops shave wall-clock relative to the S3 path
    s3 = cm.pipelined_round_cost("lifl", elems * 4, n, upload=up)
    assert model.wall_clock_s < s3.wall_clock_s
    assert model.ops.total < s3.ops.total


def test_colocated_rejected_for_non_lifl():
    with pytest.raises(ValueError, match="LIFL"):
        cm.pipelined_round_cost("gradssharding", MB, 8, 4, colocated=True)


# ---------------------------------------------------------------------------
# Bounded-memory long sessions (keep_records=False)
# ---------------------------------------------------------------------------

def test_keep_records_false_bounds_growth_keeps_aggregates():
    up = UploadModel(mbps=16.0, jitter_s=1.0, seed=3)
    cfg = SessionConfig(n_shards=4, schedule="pipelined", upload=up)
    full = FederatedSession(cfg)
    compact = FederatedSession(cfg, keep_records=False)
    rounds = 6
    for rnd in range(rounds):
        grads = _grads(6, 2_048, seed=rnd)
        a, b = full.round(grads), compact.round(grads)
        assert np.array_equal(a.avg_flat, b.avg_flat)
        assert a.wall_clock_s == b.wall_clock_s
        # compacted sessions stay flat round over round
        assert len(compact.runtime.records) == 0
        assert len(compact.runtime.avail._t) == 0
        assert compact.store.list() == []
        assert compact.store.stats.put_log == []
    # the full session grew linearly...
    assert len(full.runtime.records) == 4 * rounds
    assert len(full.store.list()) > 0
    # ...but every aggregate counter agrees exactly
    assert compact.runtime.total_gb_s() == pytest.approx(
        full.runtime.total_gb_s(), rel=1e-12)
    assert compact.store.stats.puts == full.store.stats.puts
    assert compact.store.stats.gets == full.store.stats.gets
    assert compact.session_wall_s == pytest.approx(full.session_wall_s)
    assert compact.total_cost() == pytest.approx(full.total_cost())


def test_compacted_warm_pool_survives():
    """Compaction must not forget warm containers: round 1 still reuses
    round 0's families."""
    session = FederatedSession(SessionConfig(n_shards=4,
                                             keep_records=False))
    session.round(_grads())
    r1 = session.round(_grads(seed=1))
    assert not any(rec.cold_start for rec in r1.records)


def test_runtime_reset_clears_cumulative_billing():
    rt = LambdaRuntime()
    rt.invoke(lambda ctx: None, fn_name="f", memory_mb=1024)
    assert rt.total_gb_s() > 0
    rt.reset()
    assert rt.total_gb_s() == 0.0


# ---------------------------------------------------------------------------
# One result buffer per round: the kernel fold divides straight into
# avg_flat, and the read-back hands that buffer out without a copy
# ---------------------------------------------------------------------------

def _readback_values(session, rnd):
    return [session.store.peek(k)
            for k in session.store.list(f"round{rnd:05d}/avg/")]


@pytest.mark.parametrize("keep_records", [True, False],
                         ids=["records", "compacted"])
def test_kernel_round_hands_out_the_fold_buffer(monkeypatch, keep_records):
    """On the kernel path with a uniform plan, avg_flat is the buffer the
    fold divided into: the avg-shard objects are its views. Every round's
    buffer is new, shares no memory with another round's or with the
    contributions, stays writeable and keeps its values after later
    rounds, and is bit-identical to the streaming engine."""
    monkeypatch.setenv("REPRO_AGG_PALLAS", "1")
    kernel = FederatedSession(SessionConfig(n_shards=3,
                                            keep_records=keep_records))
    ref = FederatedSession(SessionConfig(n_shards=3, engine="streaming"))
    rounds = [_grads(n=3, size=10_000, seed=s) for s in range(3)]
    results, kept = [], []
    for rnd, grads in enumerate(rounds):
        got = kernel.round(grads)
        assert got.kernel_folds == 3
        assert np.array_equal(got.avg_flat, ref.round(grads).avg_flat)
        shards = _readback_values(kernel, rnd)
        if keep_records:
            assert len(shards) == 3
            assert all(sh.base is got.avg_flat for sh in shards)
            assert all(np.shares_memory(sh, got.avg_flat) for sh in shards)
        else:
            assert shards == []
        assert got.avg_flat.flags.writeable
        assert not any(np.shares_memory(got.avg_flat, g) for g in grads)
        assert not any(np.shares_memory(got.avg_flat, r.avg_flat)
                       for r in results)
        results.append(got)
        kept.append(got.avg_flat.copy())
    assert all(np.array_equal(r.avg_flat, k) for r, k in zip(results, kept))


SIZES = [3_000, 40, 2_500, 4_460]          # 10,000 elements, four tensors


@pytest.mark.parametrize("kernel, options, hands_out", [
    pytest.param(False, {}, False, id="numpy-evaluator"),
    pytest.param(True, dict(topology="sharded_tree", n_shards=2), False,
                 id="sharded_tree"),
    pytest.param(True, dict(partition="balanced", n_shards=2,
                            tensor_sizes=SIZES), False, id="balanced"),
    pytest.param(True, dict(partition="layer_contiguous", n_shards=6,
                            tensor_sizes=SIZES), False,
                 id="layer_contiguous-empty-shard"),
    pytest.param(True, dict(partition="layer_contiguous", n_shards=3,
                            tensor_sizes=SIZES), True,
                 id="layer_contiguous"),
    pytest.param(True, dict(topology="lambda_fl"), None, id="lambda_fl"),
])
def test_readback_copies_unless_the_shards_are_one_fold_buffer(
        monkeypatch, kernel, options, hands_out):
    """The read-back hands out the fold's buffer only where the read-back
    values are its views at their plan segments (one segment per shard,
    every shard folded by the one kernel call); everywhere else it copies
    into a new array, as before. λ-FL's read-back is its one global value,
    which its collect returns as it is: a root the numpy evaluator wrote,
    never a fold buffer. Every case is bit-identical to streaming."""
    monkeypatch.setenv("REPRO_AGG_PALLAS", "1" if kernel else "0")
    grads = _grads(n=3, size=10_000)
    session = FederatedSession(SessionConfig(**options))
    got = session.round(grads)
    want = FederatedSession(SessionConfig(engine="streaming", **options)) \
        .round(grads)
    assert np.array_equal(got.avg_flat, want.avg_flat)
    assert (got.kernel_folds > 0) == kernel
    values = _readback_values(session, 0)
    shared = [np.shares_memory(got.avg_flat, v) for v in values]
    if hands_out is None:
        assert shared == [True] and got.avg_flat is values[0]
        assert got.avg_flat.base is None
    elif hands_out:
        assert all(shared) and all(v.base is got.avg_flat for v in values)
    else:
        assert not any(shared)


def test_kernel_sessions_share_the_process_staging(monkeypatch):
    """Kernel folds stream through the process's two staging buffers (one
    worker: the staged path): allocated in a session's first round, then
    reused by its later rounds and by every other session, and never
    handed out as part of a round's answer."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_staging", {})
    monkeypatch.setattr(ops, "STAGING_BYTES", 4 * 3 * 32 * 128)  # one tile
    monkeypatch.setenv("REPRO_AGG_PALLAS", "1")
    grads = _grads(n=3, size=10_000)
    first = FederatedSession(SessionConfig(n_shards=3, workers=1))
    results = [first.round(grads)]
    buffers = dict(ops._staging)
    assert len(buffers) == 2
    results.append(first.round(grads))
    second = FederatedSession(SessionConfig(n_shards=3, workers=1))
    results.append(second.round(grads))
    assert ops._staging.keys() == buffers.keys()
    assert all(ops._staging[k] is b for k, b in buffers.items())
    for r in results:
        assert r.kernel_folds == 3
        assert np.array_equal(r.avg_flat, results[0].avg_flat)
        assert not any(np.shares_memory(r.avg_flat, b)
                       for b in buffers.values())

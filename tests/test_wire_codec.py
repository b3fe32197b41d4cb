"""WireCodec: the pluggable on-the-wire representation API.

The contracts under test: (1) the identity codec is byte-for-byte
invisible — bit-identity to the raw reference across the full topology ×
engine × schedule × readahead grid; (2) lossy codecs are *deterministic*
— encode/decode are pure functions, so ``avg_flat`` and ``codec_error``
are bit-identical across engines, schedules, read-ahead windows and
arrival permutations; (3) the numpy codec mirrors replay the Pallas
kernels' f32 op sequence (exactly on the tested inputs; the qsgd8 scale
divide can differ in the last bit elsewhere); (4) every modeled platform quantity
(upload bytes, GET bytes, billing, feasibility) sees wire sizes, with
``pipelined_round_cost`` matching the event sim to float epsilon per
codec; (5) op *counts* never change — compression moves bytes, not ops.
"""
import dataclasses

import numpy as np
import pytest

from repro.api import FederatedSession, SessionConfig
from repro.core import cost_model as cm
from repro.core import wire_codec as wc
from repro.core.cost_model import UploadModel
from repro.serverless import LambdaRuntime

MB = 1024 * 1024
ENGINES = ("streaming", "batched", "incremental")
LOSSY = ("fp16", "qsgd8", "topk")
CODECS = ("identity",) + LOSSY

JITTER = UploadModel(mbps=16.0, jitter_s=3.0, rate_jitter=0.5, seed=11)


def _grads(n=12, size=5_003, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(size).astype(np.float32) for _ in range(n)]


def _round(topology, grads, **kw):
    return FederatedSession(topology=topology, **kw).round(grads)


@dataclasses.dataclass(frozen=True)
class FixedStarts(UploadModel):
    starts: tuple = ()

    def plan(self, n, rnd=0):
        return np.asarray(self.starts, float), np.ones(n)


# ---------------------------------------------------------------------------
# Registry + knob resolution
# ---------------------------------------------------------------------------

def test_codec_registry_and_knob(monkeypatch):
    monkeypatch.delenv("REPRO_AGG_CODEC", raising=False)
    assert wc.get_codec(None).name == "identity"
    assert wc.get_codec("auto").name == "identity"
    assert wc.get_codec("qsgd8").name == "qsgd8"
    inst = wc.get_codec("fp16")
    assert wc.get_codec(inst) is inst
    monkeypatch.setenv("REPRO_AGG_CODEC", "fp16")
    assert wc.get_codec(None).name == "fp16"
    assert wc.get_codec("topk").name == "topk"       # explicit wins
    assert set(CODECS) <= set(wc.available_codecs())
    with pytest.raises(ValueError, match="unknown wire codec"):
        wc.get_codec("gzip-hope")


def test_codec_registry_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        @wc.register_codec("identity")
        class Clash(wc.WireCodec):
            pass

    @wc.register_codec("identity", replace=True)
    class Replaced(wc.IdentityCodec):
        pass
    try:
        assert isinstance(wc.get_codec("identity"), Replaced)
    finally:
        wc.register_codec("identity", replace=True)(wc.IdentityCodec)
    assert type(wc.get_codec("identity")) is wc.IdentityCodec


def test_env_codec_reaches_the_round(monkeypatch):
    monkeypatch.setenv("REPRO_AGG_CODEC", "fp16")
    r = _round("gradssharding", _grads(4, 1_024), n_shards=2)
    assert r.codec == "fp16" and r.codec_error > 0.0
    r = _round("gradssharding", _grads(4, 1_024), n_shards=2,
               codec="identity")                     # explicit wins
    assert r.codec == "identity" and r.codec_error == 0.0


def test_session_validates_codec_eagerly():
    with pytest.raises(ValueError, match="unknown wire codec"):
        FederatedSession(SessionConfig(codec="gzip-hope"))


# ---------------------------------------------------------------------------
# Round-trip determinism + chunked decode == full decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [100, 4_096, 5_003, 12_288])
@pytest.mark.parametrize("codec", LOSSY)
def test_encode_decode_idempotent(codec, size):
    """decode∘encode is a projection: encoding its own output is a fixed
    point, so repeated wire round-trips never drift."""
    c = wc.get_codec(codec)
    x = _grads(1, size, seed=3)[0]
    once = c.decode(c.encode(x))
    twice = c.decode(c.encode(once))
    assert np.array_equal(once, twice)
    # and encoding is deterministic
    a, b = c.encode(x), c.encode(x)
    for part in a.parts:
        assert np.array_equal(a.parts[part], b.parts[part])


@pytest.mark.parametrize("codec", LOSSY)
def test_decode_range_matches_full_decode(codec):
    c = wc.get_codec(codec)
    x = _grads(1, 13_111, seed=5)[0]
    p = c.encode(x)
    full = c.decode(p)
    for step in (1_000, 4_096, 7_777):
        got = np.concatenate([c.decode_range(p, s, min(s + step, x.size))
                              for s in range(0, x.size, step)])
        assert np.array_equal(got, full)
    view = wc.EncodedView(c, p)
    assert np.array_equal(view.read(100, 9_000), full[100:9_000])
    assert np.array_equal(view.materialize(), full)


def test_empty_shard_payloads():
    for codec in LOSSY:
        c = wc.get_codec(codec)
        p = c.encode(np.empty(0, np.float32))
        assert p.nbytes == 0 and c.decode(p).size == 0


@pytest.mark.parametrize("codec,ratio", [("fp16", 2.0), ("qsgd8", 3.9),
                                         ("topk", 10.0)])
def test_wire_bytes_shrink(codec, ratio):
    c = wc.get_codec(codec)
    nb = 1_000_000 * 4
    assert c.wire_bytes(nb) * ratio <= nb
    assert wc.get_codec("identity").wire_bytes(nb) == nb


# ---------------------------------------------------------------------------
# Numpy mirrors == Pallas kernels (interpret mode on CPU hosts)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("size", [4_096, 5_003])
def test_qsgd8_matches_pallas_kernel(size):
    from repro.kernels import ops
    c = wc.get_codec("qsgd8")
    x = _grads(1, size, seed=7)[0]
    p = c.encode(x)
    codes, scales, l = ops.qsgd_compress(x)
    assert np.array_equal(p.parts["codes"],
                          np.asarray(codes).reshape(-1)[:size])
    assert np.array_equal(p.parts["scales"], np.asarray(scales).reshape(-1))
    assert np.array_equal(c.decode(p),
                          np.asarray(ops.qsgd_decompress(codes, scales, l)))


@pytest.mark.slow
@pytest.mark.parametrize("size", [4_096, 5_003])
def test_topk_matches_pallas_kernel(size):
    from repro.kernels import ops
    c = wc.get_codec("topk")
    x = _grads(1, size, seed=9)[0]
    dense = np.asarray(ops.topk_sparsify(x, c.k_per_block))
    assert np.array_equal(c.decode(c.encode(x)), dense)


# ---------------------------------------------------------------------------
# Identity: bit-identical by construction across the whole grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topology,kw", [
    ("gradssharding", {"n_shards": 4}),
    ("lambda_fl", {}),
    ("lifl", {}),
    ("sharded_tree", {"n_shards": 4}),
])
def test_identity_codec_is_invisible(topology, kw):
    grads = _grads()
    ref = _round(topology, grads, codec="identity", **kw)
    assert ref.codec == "identity" and ref.codec_error == 0.0
    for engine in ENGINES:
        for schedule, k in (("barrier", None), ("pipelined", 1),
                            ("pipelined", 4)):
            r = _round(topology, grads, engine=engine, schedule=schedule,
                       readahead_k=k, upload=JITTER, codec="identity", **kw)
            assert np.array_equal(r.avg_flat, ref.avg_flat)
            assert (r.puts, r.gets) == (ref.puts, ref.gets)


# ---------------------------------------------------------------------------
# Lossy codecs: deterministic across engines, schedules, k, arrivals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", LOSSY)
@pytest.mark.parametrize("topology,kw", [
    ("gradssharding", {"n_shards": 4}),
    ("lambda_fl", {}),
    ("lifl", {"colocated": True}),
    ("sharded_tree", {"n_shards": 4}),
])
def test_lossy_codec_deterministic_across_grid(topology, kw, codec):
    grads = _grads()
    ref = _round(topology, grads, codec=codec, **kw)
    assert ref.codec == codec
    assert 0.0 < ref.codec_error < 10.0
    for engine in ENGINES:
        for schedule, k in (("barrier", None), ("pipelined", 1),
                            ("pipelined", 8)):
            r = _round(topology, grads, engine=engine, schedule=schedule,
                       readahead_k=k, upload=JITTER, codec=codec, **kw)
            assert np.array_equal(r.avg_flat, ref.avg_flat), \
                f"{codec} moved bits under {engine}/{schedule}/k={k}"
            assert r.codec_error == ref.codec_error
            assert (r.puts, r.gets) == (ref.puts, ref.gets), \
                "codecs change bytes, never op counts"


def test_codec_error_deterministic_across_arrival_permutations():
    n = 9
    grads = _grads(n, 4_096, seed=2)
    ref = _round("gradssharding", grads, n_shards=4, codec="qsgd8")
    for perm_seed in (1, 2, 3):
        order = np.random.default_rng(perm_seed).permutation(n) * 3.0
        up = FixedStarts(mbps=16.0, starts=tuple(float(t) for t in order))
        r = _round("gradssharding", grads, n_shards=4, codec="qsgd8",
                   schedule="pipelined", upload=up, readahead_k=4)
        assert r.codec_error == ref.codec_error
        assert np.array_equal(r.avg_flat, ref.avg_flat)


def test_codec_error_ordering():
    """Aggressiveness ordering on random data: fp16 < qsgd8 < topk."""
    grads = _grads(8, 8_192, seed=4)
    errs = {codec: _round("gradssharding", grads, n_shards=4,
                          codec=codec).codec_error for codec in CODECS}
    assert errs["identity"] == 0.0
    assert 0.0 < errs["fp16"] < errs["qsgd8"] < errs["topk"]


# ---------------------------------------------------------------------------
# The platform sees wire bytes: store, op logs, GETs, uploads
# ---------------------------------------------------------------------------

def test_store_holds_payloads_and_accounts_wire_bytes():
    n, size, m = 8, 8_192, 4
    grads = _grads(n, size)
    raw = n * size * 4
    session = FederatedSession(topology="gradssharding", n_shards=m,
                               codec="qsgd8")
    r = session.round(grads)
    stats = session.store.stats
    upload_put = [(k, nb) for k, nb in stats.put_log if "/client" in k]
    assert len(upload_put) == n * m
    wire = sum(nb for _, nb in upload_put)
    assert raw / 4.2 < wire < raw / 3.8, "qsgd8 must shrink uploads ~4x"
    # stored objects ARE payloads, sized at wire bytes; outputs stay raw
    for key, _ in upload_put:
        v = session.store.peek(key)
        assert isinstance(v, wc.WirePayload)
        assert v.nbytes == wc.get_codec("qsgd8").wire_bytes(v.raw_nbytes)
    for key in session.store.list():
        if "/avg/" in key:
            assert isinstance(session.store.peek(key), np.ndarray)
    # aggregator GETs read wire bytes too (read-back of raw outputs rides
    # on top), and op counts match the raw Table II entries
    expect = cm.s3_ops("gradssharding", n, m)
    assert (r.puts, r.gets) == (expect.puts, expect.gets)
    agg_read = sum(nb for k, nb in stats.get_log if "/client" in k)
    assert agg_read == wire


def test_records_read_wire_bytes():
    n, size = 6, 16_384
    grads = _grads(n, size)
    r_id = _round("lambda_fl", grads, codec="identity")
    r_q = _round("lambda_fl", grads, codec="qsgd8")
    leaf_id = [rec for rec in r_id.records if "leaf" in rec.fn_name]
    leaf_q = [rec for rec in r_q.records if "leaf" in rec.fn_name]
    assert sum(r.read_bytes for r in leaf_q) * 3.8 < \
        sum(r.read_bytes for r in leaf_id)
    # decode work is charged: leaf compute time grows vs identity
    assert sum(r.compute_s for r in leaf_q) > \
        sum(r.compute_s for r in leaf_id)


# ---------------------------------------------------------------------------
# Cost model: sim == model parity per codec, feasibility, billing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 4, 8])
@pytest.mark.parametrize("codec", CODECS)
def test_pipelined_cost_matches_sim_per_codec(codec, k):
    n, elems, m = 12, 65_536, 4
    sim = _round("gradssharding", _grads(n, elems), n_shards=m,
                 schedule="pipelined", upload=JITTER, readahead_k=k,
                 codec=codec)
    model = cm.pipelined_round_cost("gradssharding", elems * 4, n, m,
                                    upload=JITTER, readahead_k=k,
                                    codec=codec)
    assert model.wall_clock_s == pytest.approx(sim.wall_clock_s, rel=1e-9)
    billed = sum(rec.billed_gb_s for rec in sim.records)
    assert model.lambda_gb_s == pytest.approx(billed, rel=1e-3)
    assert {rec.memory_mb for rec in sim.records} >= {model.memory_mb}


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("topology,m,kw", [
    ("lambda_fl", 1, {}), ("lifl", 1, {}), ("sharded_tree", 8,
                                            {"n_shards": 8}),
])
def test_cost_parity_other_topologies(topology, m, kw, codec):
    n, elems = 12, 32_768
    sim_p = _round(topology, _grads(n, elems), schedule="pipelined",
                   upload=JITTER, codec=codec, **kw)
    sim_b = _round(topology, _grads(n, elems), schedule="barrier",
                   upload=JITTER, codec=codec, **kw)
    pc = cm.pipelined_round_cost(topology, elems * 4, n, m, upload=JITTER,
                                 codec=codec)
    bc = cm.barrier_round_cost(topology, elems * 4, n, m, upload=JITTER,
                               codec=codec)
    assert pc.wall_clock_s == pytest.approx(sim_p.wall_clock_s, rel=1e-9)
    assert bc.wall_clock_s == pytest.approx(sim_b.wall_clock_s, rel=1e-9)


def test_colocated_cost_parity_with_codec():
    n, elems = 12, 32_768
    sim = _round("lifl", _grads(n, elems), schedule="pipelined",
                 upload=JITTER, colocated=True, codec="qsgd8",
                 readahead_k=4)
    model = cm.pipelined_round_cost("lifl", elems * 4, n, upload=JITTER,
                                    colocated=True, codec="qsgd8",
                                    readahead_k=4)
    assert model.wall_clock_s == pytest.approx(sim.wall_clock_s, rel=1e-9)


def test_qsgd8_flips_feasibility_at_the_ceiling():
    """The paper's 10,240 MB wall: a gradient the raw 3x formula rejects
    fits once the prefetch window buffers int8 payloads."""
    limits = LambdaRuntime().limits
    gb = int(4_000 * MB)                  # 3x4000+450 > 10240 > 2.25x4000+450
    # (codec pinned everywhere: codec=None legitimately resolves the
    # REPRO_AGG_CODEC env, so the default call is not env-hermetic)
    assert not cm.feasible("lambda_fl", gb, limits=limits, codec="identity")
    assert cm.feasible("lambda_fl", gb, limits=limits, codec="qsgd8")
    assert cm.feasible("lambda_fl", gb, limits=limits, codec="fp16")
    # the RoundCost records agree
    assert not cm.pipelined_round_cost("lambda_fl", gb, 20, upload=JITTER,
                                       codec="identity").feasible
    assert cm.pipelined_round_cost("lambda_fl", gb, 20, upload=JITTER,
                                   codec="qsgd8").feasible
    # max_feasible_grad_mb stays the raw-wire wall
    assert gb / MB > cm.max_feasible_grad_mb(limits)


def test_wire_alloc_identity_reduces_to_legacy_formula():
    limits = LambdaRuntime().limits
    for k in (1, 2, 4, 8):
        legacy = cm.readahead_alloc_mult(k, 20, limits) * 1000
        assert cm.wire_alloc_bytes(1000, limits, k, 20, None) == legacy
        assert cm.wire_alloc_bytes(1000, limits, k, 20, 1000) == legacy
    # lossy: accumulator + decode target full-size, (k-1) window buffers
    # at wire size (the frontier buffer is the decode target)
    assert cm.wire_alloc_bytes(1000, limits, 1, 20, 250) == 2000
    assert cm.wire_alloc_bytes(1000, limits, 4, 20, 250) == 2750
    # weighted folds carry an f64 accumulator: one extra input of budget
    assert cm.wire_alloc_bytes(1000, limits, 1, 20, 250,
                               weighted=True) == 3000


def test_client_upload_bytes_entries():
    gb = 4_096 * 4
    q = wc.get_codec("qsgd8")
    assert cm.client_upload_bytes("lambda_fl", gb, codec="identity") == gb
    assert cm.client_upload_bytes("lambda_fl", gb, codec="qsgd8") == \
        q.wire_bytes(gb)
    # sharded: M independently framed shards
    per_shard = [q.wire_bytes(b) for b in cm.uniform_shard_bytes(gb, 4)]
    assert cm.client_upload_bytes("gradssharding", gb, 4,
                                  codec="qsgd8") == sum(per_shard)
    assert cm.client_upload_bytes("sharded_tree", gb, 4,
                                  codec="qsgd8") == sum(per_shard)
    assert cm.client_upload_bytes("gradssharding", gb, 4,
                                  codec="identity") == gb


# ---------------------------------------------------------------------------
# Composition: faults, multi-round sessions, keep_records
# ---------------------------------------------------------------------------

def test_codec_composes_with_faults_and_retries():
    from repro.serverless import FaultPlan
    grads = _grads(8, 2_048)
    ref = _round("gradssharding", grads, n_shards=4, codec="qsgd8")
    faults = FaultPlan(fail={("r0-shard1", 0)})
    session = FederatedSession(SessionConfig(n_shards=4, codec="qsgd8"),
                               faults=faults)
    r = session.round(grads)
    assert np.array_equal(r.avg_flat, ref.avg_flat)
    assert any(rec.failed for rec in session.runtime.records)


def test_unregistered_codec_instance_round_trips():
    """The knob accepts a WireCodec *instance*: payloads decode through
    the object that encoded them, never a registry lookup by name — an
    unregistered custom codec works, and one that shadows a registered
    name cannot be mis-decoded through the registry entry."""
    class Doubling(wc.Fp16Codec):
        name = "fp16"                      # deliberate name collision

        def decode_range(self, payload, start, stop):
            return 2.0 * super().decode_range(payload, start, stop)

        def decode(self, payload):
            return self.decode_range(payload, 0, payload.n_elems)

    from repro.core.aggregation import aggregate_round
    from repro.store import ObjectStore
    grads = _grads(4, 2_048)
    for engine in ENGINES:
        store, rt = ObjectStore(), LambdaRuntime()
        r = aggregate_round("gradssharding", grads, rnd=0, store=store,
                            runtime=rt, n_shards=2, engine=engine,
                            codec=Doubling())
        ref = _round("gradssharding", grads, n_shards=2, codec="fp16",
                     engine=engine)
        assert np.array_equal(r.avg_flat, 2.0 * ref.avg_flat), engine


def test_lifl_weighted_feasibility_matches_sim_oom():
    """Regression: the model must not green-light a compressed-wire LIFL
    config its own event sim OOMs on — LIFL's level-1 folds are weighted
    (f64 accumulator), and feasible()/lambda_memory_mb budget that extra
    buffer through the cost_wire_weighted hook."""
    import dataclasses as dc

    from repro.core.aggregation import aggregate_round
    from repro.store import ObjectStore
    grad_b = 4 * MB                 # weighted bound: 3*4 + 450 = 462 MB
    grads = _grads(8, grad_b // 4, seed=1)

    def runs_under(ceiling_mb):
        limits = dc.replace(LambdaRuntime().limits,
                            max_memory_mb=ceiling_mb)
        feas = cm.feasible("lifl", grad_b, limits=limits, codec="qsgd8")
        store, rt = ObjectStore(), LambdaRuntime(limits=limits)
        try:
            aggregate_round("lifl", grads, rnd=0, store=store, runtime=rt,
                            schedule="pipelined", upload=JITTER,
                            codec="qsgd8")
            ran = True
        except Exception:
            ran = False
        return feas, ran

    # either side of the weighted bound, model verdict == sim outcome
    # (the unweighted 2-buffer bound would green-light 460 and OOM)
    assert runs_under(460) == (False, False)
    assert runs_under(463) == (True, True)
    # unweighted folds keep the tighter 2-buffer bound
    assert cm.lambda_memory_mb("lambda_fl", grad_b, codec="qsgd8") < \
        cm.lambda_memory_mb("lifl", grad_b, codec="qsgd8")


def test_legacy_plugin_cost_hooks_rejected_with_migration_error():
    """The v1 signature-sniffing back-compat is gone: a plugin whose cost
    hooks predate the v2 keyword-only protocol (no ``codec=``) gets a
    pointed migration error under *every* codec — identity included —
    instead of working by accident until someone flips the codec knob."""
    from repro.core import topology as topo

    @topo.register_topology("_legacy_hooks")
    class Legacy(topo.Topology):
        def cost_s3_ops(self, n, m=1):
            return cm.S3Ops(n, n, n)

        def cost_collect_fanin(self, n, m=1):
            return n

        def cost_phase_plan(self, grad_bytes, n, m, limits):  # pre-codec
            return [(cm.aggregator_timing(grad_bytes, n, grad_bytes,
                                          limits), 1)]

    try:
        for codec in ("identity", "qsgd8"):
            with pytest.raises(TypeError, match="v2 cost-hook protocol"):
                cm.round_cost("_legacy_hooks", MB, 8, codec=codec)
    finally:
        del topo._REGISTRY["_legacy_hooks"]


def test_declared_v1_plugin_rejected_even_with_codec_kwarg():
    """Declaring ``cost_api_version = 1`` opts a plugin out of the v2
    contract explicitly — the cost model refuses it up front, before
    calling any hook."""
    from repro.core import topology as topo

    @topo.register_topology("_v1_hooks")
    class V1(topo.Topology):
        cost_api_version = 1

        def cost_s3_ops(self, n, m=1):
            return cm.S3Ops(n, n, n)

        def cost_collect_fanin(self, n, m=1):
            return n

        def cost_phase_plan(self, grad_bytes, n, m, limits, *, codec):
            return [(cm.aggregator_timing(grad_bytes, n, grad_bytes,
                                          limits), 1)]

    try:
        with pytest.raises(TypeError, match="cost_api_version=1"):
            cm.round_cost("_v1_hooks", MB, 8, codec="identity")
    finally:
        del topo._REGISTRY["_v1_hooks"]


def test_track_codec_error_opt_out():
    grads = _grads(4, 2_048)
    r = _round("gradssharding", grads, n_shards=2, codec="qsgd8",
               track_codec_error=False)
    assert np.isnan(r.codec_error)          # never a misleading 0.0
    on = _round("gradssharding", grads, n_shards=2, codec="qsgd8")
    assert np.array_equal(r.avg_flat, on.avg_flat)
    assert on.codec_error > 0.0


def test_codec_multi_round_session():
    grads_by_round = [_grads(6, 4_096, seed=100 + i) for i in range(3)]
    session = FederatedSession(SessionConfig(
        n_shards=4, schedule="pipelined", codec="fp16", upload=JITTER,
        keep_records=False))
    results = list(session.run(lambda rnd: grads_by_round[rnd], 3))
    assert all(r.codec == "fp16" for r in results)
    assert len({r.codec_error for r in results}) == 3   # per-round data
    assert session.summary()["codec"] == "fp16"

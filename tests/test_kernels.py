"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret=True executes kernel bodies on CPU)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # bare env: deterministic fallback
    from _hypothesis_fallback import given, settings
    from _hypothesis_fallback import strategies as st

import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# fedavg_stream
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 7, 20])
@pytest.mark.parametrize("l", [128, 4096, 5000, 12_345])
def test_fedavg_shapes(n, l):
    shards = jnp.asarray(RNG.standard_normal((n, l)), jnp.float32)
    out = ops.fedavg_shards(shards)
    expect = np.mean(np.asarray(shards, np.float64), axis=0)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_dtypes(dtype):
    shards = jnp.asarray(RNG.standard_normal((5, 2048)), dtype)
    out = ops.fedavg_shards(shards)
    assert out.dtype == jnp.float32          # f32 accumulate regardless
    expect = np.mean(np.asarray(shards, np.float32), axis=0)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, expect, rtol=tol, atol=tol)


def test_fedavg_weighted():
    shards = jnp.asarray(RNG.standard_normal((4, 1000)), jnp.float32)
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    out = ops.fedavg_shards(shards, w)
    expect = np.average(np.asarray(shards, np.float64), axis=0,
                        weights=np.asarray(w))
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


def test_fedavg_matches_serverless_streaming_order():
    """The kernel and the serverless numpy path share accumulation order;
    results agree to f32 division rounding (≤1 ulp)."""
    from repro.core.fedavg import streaming_mean
    shards_np = RNG.standard_normal((20, 3000)).astype(np.float32)
    serverless = streaming_mean(list(shards_np))
    kernel = np.asarray(ops.fedavg_shards(jnp.asarray(shards_np)))
    np.testing.assert_allclose(kernel, serverless, rtol=2e-7, atol=1e-9)


@pytest.mark.parametrize("weighted", [False, True])
def test_fedavg_stream_sum_matches_ref(weighted):
    """The kernel returns the client-order (weighted) sum — weights read
    from SMEM per grid step — exactly as the oracle accumulates it."""
    from repro.kernels.fedavg_stream import fedavg_stream
    x = jnp.asarray(RNG.standard_normal((5, 64, 128)), jnp.float32)
    w = jnp.asarray([0.5, 2.0, 1.0, 3.0, 0.25], jnp.float32) \
        if weighted else None
    got = fedavg_stream(x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.fedavg_stream_ref(x, w)))


@given(n=st.integers(1, 12), blocks=st.integers(1, 5),
       extra=st.integers(0, 4095))
@settings(max_examples=20, deadline=None)
def test_fedavg_property(n, blocks, extra):
    l = blocks * 4096 + extra
    shards = jnp.asarray(RNG.standard_normal((n, l)), jnp.float32)
    out = ops.fedavg_shards(shards)
    assert out.shape == (l,)
    np.testing.assert_allclose(
        out, np.mean(np.asarray(shards, np.float64), axis=0),
        rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# qsgd quantize / dequantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l", [4096, 10_000, 131_072])
def test_qsgd_roundtrip_error_bound(l):
    x = jnp.asarray(RNG.standard_normal(l), jnp.float32)
    codes, scales, n = ops.qsgd_compress(x)
    xr = ops.qsgd_decompress(codes, scales, n)
    assert codes.dtype == jnp.int8
    err = np.max(np.abs(np.asarray(x) - np.asarray(xr)))
    assert err <= float(jnp.max(scales)) / 2 + 1e-7


def test_qsgd_matches_ref():
    x = jnp.asarray(RNG.standard_normal(8192), jnp.float32)
    codes, scales, _ = ops.qsgd_compress(x)
    tiles = x.reshape(-1, 128)
    rc, rs = ref.quantize_ref(tiles)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(rc))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(rs), rtol=1e-6)
    deq = ops.qsgd_decompress(codes, scales, 8192)
    rdq = ref.dequantize_ref(rc, rs).reshape(-1)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(rdq), rtol=1e-6)


def test_qsgd_zero_block_safe():
    x = jnp.zeros(8192, jnp.float32)
    codes, scales, n = ops.qsgd_compress(x)
    xr = ops.qsgd_decompress(codes, scales, n)
    np.testing.assert_array_equal(np.asarray(xr), 0.0)


# ---------------------------------------------------------------------------
# top-k sparsify
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 10, 100, 1000])
def test_topk_keeps_k_per_block(k):
    x = jnp.asarray(RNG.standard_normal(4096), jnp.float32)
    out = np.asarray(ops.topk_sparsify(x, k))
    nnz = int(np.sum(out != 0))
    assert k <= nnz <= k + 8                  # bisection tie slack
    # survivors are the largest magnitudes
    kept = np.abs(np.asarray(x))[out != 0].min()
    dropped = np.abs(np.asarray(x))[out == 0]
    if dropped.size:
        assert kept >= dropped.max() - 1e-6


def test_topk_matches_ref():
    x = jnp.asarray(RNG.standard_normal(8192), jnp.float32)
    out = ops.topk_sparsify(x, 64)
    expect = ref.topk_sparsify_ref(x.reshape(-1, 128), 64).reshape(-1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,d", [(8, 128), (33, 256), (128, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, d, dtype):
    x = jnp.asarray(RNG.standard_normal((rows, d)), dtype)
    g = jnp.asarray(RNG.standard_normal(d), jnp.float32)
    out = ops.rmsnorm(x, g)
    expect = ref.rmsnorm_ref(x, g)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=1e-5 if dtype == jnp.float32 else 2e-2, atol=1e-5)


def test_rmsnorm_matches_model_layer():
    from repro.models.layers import rmsnorm as model_rmsnorm
    x = jnp.asarray(RNG.standard_normal((16, 64)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(64), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, g)),
                               np.asarray(model_rmsnorm(x, g)),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fused sgd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l", [4096, 5000])
def test_fused_sgd(l):
    p = jnp.asarray(RNG.standard_normal(l), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(l), jnp.float32)
    v = jnp.asarray(RNG.standard_normal(l), jnp.float32)
    pr, vr = ref.fused_sgd_ref(p, g, v, lr=0.01, momentum=0.9)
    po, vo = ops.sgd_momentum_update(p, g, v, lr=0.01, momentum=0.9)
    # rtol/atol cover XLA fma-vs-separate rounding (~1 ulp of the operands)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), rtol=1e-5,
                               atol=1e-6)


def test_fused_sgd_multi_step_matches_optimizer():
    """The kernel iterated = the pytree SGD optimizer on a flat vector."""
    from repro.optim import sgd, apply_updates
    opt = sgd(0.05, momentum=0.9)
    p_ref = jnp.asarray(RNG.standard_normal(2048), jnp.float32)
    v_ref = opt.init(p_ref)
    p_k = p_ref
    v_k = jnp.zeros_like(p_ref)
    for i in range(5):
        g = jnp.asarray(RNG.standard_normal(2048), jnp.float32)
        upd, v_ref = opt.update(g, v_ref)
        p_ref2 = apply_updates(p_ref, upd)
        p_k, v_k = ops.sgd_momentum_update(p_k, g, v_k, lr=0.05,
                                           momentum=0.9)
        np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_ref2),
                                   rtol=1e-5, atol=1e-6)
        p_ref = p_ref2

"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's exact numerical semantics (accumulation
order, f32 intermediate precision, per-block granularity) so tests can
assert tight tolerances — exact equality for order-matched fp32 paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128
QMAX = 127.0


def fedavg_stream_ref(stacked: jax.Array,
                      weights: jax.Array | None = None) -> jax.Array:
    """(N, R, 128) -> (R, 128): client-at-a-time (weighted) sum."""
    if weights is None:
        weights = jnp.ones((stacked.shape[0],), jnp.float32)
    acc = stacked[0].astype(jnp.float32) * weights[0]
    for i in range(1, stacked.shape[0]):
        acc = acc + stacked[i].astype(jnp.float32) * weights[i]
    return acc


def quantize_ref(x: jax.Array, block_rows: int = 32):
    r, lanes = x.shape
    nb = r // block_rows
    xb = x.astype(jnp.float32).reshape(nb, block_rows * lanes)
    amax = jnp.max(jnp.abs(xb), axis=1)
    scales = jnp.where(amax > 0, amax / QMAX, 1.0)
    q = jnp.clip(jnp.round(xb / scales[:, None]), -QMAX, QMAX)
    return (q.reshape(r, lanes).astype(jnp.int8),
            scales[:, None].astype(jnp.float32))


def dequantize_ref(codes: jax.Array, scales: jax.Array,
                   block_rows: int = 32) -> jax.Array:
    r, lanes = codes.shape
    nb = r // block_rows
    cb = codes.astype(jnp.float32).reshape(nb, block_rows * lanes)
    return (cb * scales).reshape(r, lanes)


def topk_sparsify_ref(x: jax.Array, k_per_block: int,
                      block_rows: int = 32) -> jax.Array:
    """Block-local top-k by magnitude; threshold = k-th largest |x| in the
    block; ties at the threshold kept (matches the kernel's >= mask)."""
    r, lanes = x.shape
    nb = r // block_rows
    xb = x.astype(jnp.float32).reshape(nb, block_rows * lanes)
    ax = jnp.abs(xb)
    kth = jnp.sort(ax, axis=1)[:, -k_per_block][:, None]
    return jnp.where(ax >= kth, xb, 0.0).reshape(r, lanes)


def rmsnorm_ref(x: jax.Array, gamma: jax.Array,
                eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)


def fused_sgd_ref(params: jax.Array, grads: jax.Array, velocity: jax.Array,
                  lr: float, momentum: float = 0.9):
    v = momentum * velocity + grads.astype(jnp.float32)
    p = (params.astype(jnp.float32) - lr * v).astype(params.dtype)
    return p, v

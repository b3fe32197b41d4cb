"""Pallas TPU kernel: block-wise magnitude top-k sparsification.

Aji & Heafield-style top-k gradient sparsification (paper Related Work,
composable with GradsSharding per shard). Global top-k needs a global sort —
hostile to both TPUs and the independent-shard-aggregator model — so we use
the standard block-local relaxation: each (block_rows × 128) tile keeps its
own top ``k_per_block`` elements by magnitude. The threshold is found with a
fixed-iteration bisection on the count (vector-ops only, no sort — lowers
cleanly to the VPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANES = 128
BISECT_ITERS = 24


def _topk_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)
    ax = jnp.abs(x)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        count = jnp.sum((ax >= mid).astype(jnp.int32))
        # keep >= k survivors: raise lo while count still >= k
        lo = jnp.where(count >= k, mid, lo)
        hi = jnp.where(count >= k, hi, mid)
        return lo, hi

    lo0 = jnp.zeros((), jnp.float32)
    hi0 = jnp.max(ax) + 1e-12
    lo, _ = lax.fori_loop(0, BISECT_ITERS, body, (lo0, hi0))
    mask = ax >= lo
    o_ref[...] = jnp.where(mask, x, 0.0)


def topk_sparsify(x: jax.Array, k_per_block: int, *, block_rows: int = 32,
                  interpret: bool = False) -> jax.Array:
    """x: (R, 128) -> same shape with all but ~k_per_block largest-|.|
    entries per (block_rows,128) tile zeroed (ties at the threshold may keep
    slightly more than k)."""
    r, lanes = x.shape
    assert lanes == LANES and r % block_rows == 0
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k_per_block),
        grid=(r // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.float32),
        interpret=interpret,
        name="topk_sparsify",
    )(x)

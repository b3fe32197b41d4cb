"""Pallas TPU kernel: streaming (weighted) FedAvg shard accumulation.

The paper's aggregation inner loop — "read one client's shard at a time,
maintain a running sum, divide once" — re-tiled for the TPU memory
hierarchy: the shard lives in HBM as an (N, R, 128) stack of client
contributions; the grid walks (shard-row-block, client) with the client
dimension iterating fastest, so each (BR, 128) f32 accumulator block stays
resident in VMEM across all N contributions (the revisiting-output
accumulation pattern). Memory per core = one accumulator block + one
incoming block — exactly the paper's two-buffer O(|θ|/M) bound, shrunk from
Lambda-RAM scale to VMEM-tile scale.

The kernel returns the running **sum**; the single divide is the caller's
(``kernels/ops.py`` divides on the host with the numpy evaluator's f32 op),
so there is one divide rule on every backend. Accumulation order is
client-by-client per element, the serverless streaming implementation's
order exactly, so an unweighted sum is bit-identical to its f32 left-fold.
Per-client weights live in SMEM and are read as scalars per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 32


def _fold_kernel(*refs, weighted: bool):
    """Grid: (row_blocks, N); client index iterates fastest."""
    if weighted:
        x_ref, w_ref, o_ref = refs
    else:
        x_ref, o_ref = refs
    n = pl.program_id(1)
    contrib = x_ref[0].astype(jnp.float32)
    if weighted:
        contrib = contrib * w_ref[n]

    @pl.when(n == 0)
    def _init():
        o_ref[...] = contrib

    @pl.when(n > 0)
    def _accum():
        o_ref[...] += contrib


def fedavg_stream(stacked: jax.Array, weights: jax.Array | None = None, *,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = False) -> jax.Array:
    """stacked: (N, R, 128) client shards -> (R, 128) f32 sum Σ w_i x_i.

    R must be a multiple of ``block_rows`` (ops.py pads). ``weights`` is
    (N,) f32 held whole in SMEM; None = unweighted (no multiply at all).
    """
    n, r, lanes = stacked.shape
    assert lanes == LANES, f"last dim must be {LANES}, got {lanes}"
    assert r % block_rows == 0, (r, block_rows)
    in_specs = [pl.BlockSpec((1, block_rows, LANES), lambda i, j: (j, i, 0))]
    args = [stacked]
    if weights is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(weights, jnp.float32))
    return pl.pallas_call(
        functools.partial(_fold_kernel, weighted=weights is not None),
        grid=(r // block_rows, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.float32),
        interpret=interpret,
        name="fedavg_stream",
    )(*args)

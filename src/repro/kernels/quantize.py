"""Pallas TPU kernels: QSGD-style per-block int8 gradient quantization.

Paper §VI / future work: "composition with gradient compression to reduce S3
transfer volume" — each shard is quantized *before* upload (or before the
reduce-scatter on the TPU path), cutting bytes 4×. One f32 scale per
(block_rows × 128) tile; symmetric round-to-nearest (the deterministic
variant of QSGD; stochastic rounding would add an unbiasing noise input).

Inside the kernels a tile's scale travels as one (1, 128) lane row holding
the scale in every lane — a block Mosaic tiles (its last two dims equal the
array's), stored and read as a vector. The public ``scales`` stay one f32
per tile, shape (R/BR, 1).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
QMAX = 127.0


def _quant_kernel(x_ref, codes_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / QMAX, 1.0)
    scale_ref[...] = jnp.full(scale_ref.shape, scale, jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -QMAX, QMAX)
    codes_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(codes_ref, scale_ref, o_ref):
    o_ref[...] = codes_ref[...].astype(jnp.float32) * scale_ref[0]


def _scale_spec() -> pl.BlockSpec:
    return pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0))


def quantize(x: jax.Array, *, block_rows: int = 32,
             interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (R, 128) f32 -> (codes int8 (R,128), scales f32 (R/BR, 1))."""
    r, lanes = x.shape
    assert lanes == LANES and r % block_rows == 0, (x.shape, block_rows)
    nblocks = r // block_rows
    codes, scale_rows = pl.pallas_call(
        _quant_kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            _scale_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, LANES), jnp.int8),
            jax.ShapeDtypeStruct((nblocks, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return codes, scale_rows[:, 0, :1]


def dequantize(codes: jax.Array, scales: jax.Array, *, block_rows: int = 32,
               interpret: bool = False) -> jax.Array:
    r, lanes = codes.shape
    nblocks = r // block_rows
    assert scales.shape == (nblocks, 1), (scales.shape, nblocks)
    scale_rows = jnp.broadcast_to(scales[:, :, None], (nblocks, 1, LANES))
    return pl.pallas_call(
        _dequant_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            _scale_spec(),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.float32),
        interpret=interpret,
    )(codes, scale_rows)

"""The plain reference a round's average is compared with, and the comparison.

It imports nothing of the program under test. A GradsSharding round's
answer is the mean of the N contributions as the aggregators receive them:
each client's gradient is cut into M contiguous shards (the first
``total % M`` one element longer), each shard passes through the wire
codec's reference in ``chipbench/codecs/<codec>.py``, and the results are
summed in f32 in client order, then divided once by N in f32.

Contributions are equal when their bits are: :func:`ulp_gap` measures how
far apart two f32 vectors are in units in the last place.
"""

from __future__ import annotations

import importlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 22  # elements per block of the column-parallel folds
#: the gap read for a NaN, or for an answer of the wrong shape or dtype
NO_ANSWER = 2**32


def load_codec(name: str):
    """The codec reference module ``chipbench/codecs/<name>.py``."""
    try:
        return importlib.import_module(f"chipbench.codecs.{name}")
    except ModuleNotFoundError:
        raise ValueError(f"no reference for wire codec {name!r} in chipbench/codecs") from None


def shard_bounds(total: int, m: int) -> list[tuple[int, int]]:
    """[start, stop) of the M contiguous shards of a ``total``-element vector."""
    base, rem = divmod(total, m)
    bounds, off = [], 0
    for j in range(m):
        size = base + (1 if j < rem else 0)
        bounds.append((off, off + size))
        off += size
    return bounds


def received(grad: np.ndarray, m: int, codec, params: dict) -> np.ndarray:
    """What the aggregators fold of one client's gradient, laid end to end."""
    if getattr(codec, "PASSTHROUGH", False):
        return grad
    out = np.empty_like(grad)
    for a, b in shard_bounds(grad.size, m):
        out[a:b] = codec.apply(grad[a:b], params)
    return out


def _blocks(size: int) -> list[tuple[int, int]]:
    return [(s, min(s + CHUNK, size)) for s in range(0, size, CHUNK)]


def mean_f32(rows: list[np.ndarray], workers: int | None = None) -> np.ndarray:
    """f32 left fold of ``rows`` in order, divided once by len(rows) in f32."""
    size = rows[0].size
    out = np.empty(size, np.float32)
    n = np.float32(len(rows))

    def block(span):
        a, b = span
        acc = rows[0][a:b].astype(np.float32, copy=True)
        for r in rows[1:]:
            acc += r[a:b]
        np.divide(acc, n, out=out[a:b])

    with ThreadPoolExecutor(workers or os.cpu_count() or 1) as ex:
        list(ex.map(block, _blocks(size)))
    return out


def round_reference(grads: list[np.ndarray], m: int, codec_name: str,
                    params: dict) -> np.ndarray:
    """The average one GradsSharding round over ``grads`` must return."""
    codec = load_codec(codec_name)
    with ThreadPoolExecutor(os.cpu_count() or 1) as ex:
        rows = list(ex.map(lambda g: received(g, m, codec, params), grads))
    return mean_f32(rows)


def _ordered(x: np.ndarray) -> np.ndarray:
    """f32 bits mapped to int64 so that adjacent floats differ by 1 and
    -0.0 and +0.0 coincide."""
    i = x.view(np.int32).astype(np.int64)
    return np.where(i < 0, -(i & 0x7FFFFFFF), i)


def ulp_gap(got, ref: np.ndarray) -> dict:
    """How far ``got`` lies from ``ref``: the largest gap in f32 units in the
    last place, the number of elements that differ and the largest absolute
    difference. A NaN in either, or a shape or dtype other than the
    reference's, reads :data:`NO_ANSWER`."""
    got = np.asarray(got)
    if got.shape != ref.shape or got.dtype != np.float32:
        return {"max_ulp": NO_ANSWER, "differing": int(ref.size), "max_abs": float("nan")}
    if np.array_equal(got.view(np.int32), ref.view(np.int32)):
        return {"max_ulp": 0, "differing": 0, "max_abs": 0.0}
    worst, differing, max_abs = 0, 0, 0.0
    for a, b in _blocks(ref.size):
        g, r = got[a:b], ref[a:b]
        if np.isnan(g).any() or np.isnan(r).any():
            return {"max_ulp": NO_ANSWER, "differing": int(ref.size), "max_abs": float("nan")}
        gap = np.abs(_ordered(g) - _ordered(r))
        worst = max(worst, int(gap.max(initial=0)))
        differing += int(np.count_nonzero(gap))
        max_abs = max(max_abs, float(np.abs(g - r).max(initial=0.0)))
    return {"max_ulp": worst, "differing": differing, "max_abs": max_abs}

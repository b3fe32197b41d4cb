"""jit'd public wrappers for the Pallas kernels.

Handle flat-vector ⇄ (rows, 128) tiling, padding to block multiples, and
interpret-mode selection (interpret=True off TPU — the kernel bodies
execute in Python for validation; on TPU they lower to Mosaic and never
run interpreted). :func:`kernel_mode` is the one switch between these
kernels and the numpy mirrors of the aggregation engine and wire codecs.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import knobs
from repro.kernels import fedavg_stream as _fa
from repro.kernels import fused_sgd as _sgd
from repro.kernels import quantize as _q
from repro.kernels import rmsnorm as _rn
from repro.kernels import topk_sparsify as _tk
from repro.tracing import span

LANES = 128


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_mode() -> str | None:
    """Where the aggregation path's kernels run, or None for numpy.

    ``"compiled"`` on a TPU backend — never interpret mode there;
    ``"interpret"`` on any other backend when ``REPRO_AGG_PALLAS`` forces
    the kernels (CPU tests); None — the engine's and codecs' numpy
    mirrors — otherwise, and wherever ``REPRO_AGG_PALLAS=0``.
    """
    env = knobs.env_pallas()
    if env is False:
        return None
    if not _use_interpret():
        return "compiled"
    return "interpret" if env else None


def _to_tiles(flat: jax.Array, block_rows: int) -> tuple[jax.Array, int]:
    """flat (L,) -> (R, 128) padded; returns (tiles, original length)."""
    l = flat.shape[-1]
    tile = block_rows * LANES
    pad = (-l) % tile
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    r = flat.shape[-1] // LANES
    return flat.reshape(flat.shape[:-1] + (r, LANES)), l


def _from_tiles(tiles: jax.Array, l: int) -> jax.Array:
    return tiles.reshape(tiles.shape[:-2] + (-1,))[..., :l]


# ---------------------------------------------------------------------------
# fold: byte-bounded launches of the fedavg_stream kernel
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _fold_sum(tiles, weights, block_rows, interpret):
    return _fa.fedavg_stream(tiles, weights, block_rows=block_rows,
                             interpret=interpret)


def fold_budget_bytes() -> int | None:
    """Bytes one fold launch may hold on the default device: half of what
    its allocator has free (``memory_stats()``), so a launch's input and
    output fit beside whatever the previous launch is still releasing.
    None where the backend reports no limit (the CPU)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return (int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))) \
        // 2


def fold_windows(total: int, n: int, budget_bytes: int | None,
                 parts: int = 1, block_rows: int = 32) -> list:
    """Cut ``total`` fold columns of an N-client round into launch windows.

    Each window is ``[start, stop)`` with a tile-aligned start; its
    launch holds an (N, cols) f32 input and a (cols,) f32 output, cols
    padded to the kernel tile, within ``budget_bytes`` (None = no bound).
    Windows are as equal as the tiles allow and at least ``parts`` in
    number when there are enough tiles (the interpret-mode fold pool).
    """
    tile = block_rows * LANES
    n_tiles = -(-total // tile)
    if n_tiles == 0:
        return []
    cap = n_tiles
    if budget_bytes is not None:
        cap = budget_bytes // (4 * (n + 1) * tile)
        if cap < 1:
            raise ValueError(
                f"one {n}-client fold tile needs {4 * (n + 1) * tile} B, "
                f"more than the device budget of {budget_bytes} B")
    n_win = max(-(-n_tiles // cap), min(parts, n_tiles))
    per = -(-n_tiles // n_win) * tile
    return [(s, min(s + per, total)) for s in range(0, total, per)]


def _slice_rows(x, s: int, e: int):
    return x[s:e]


def fedavg_multi(shard_stacks: Sequence, weights=None,
                 block_rows: int = 32,
                 interpret: bool | None = None,
                 workers: int | str | None = None,
                 read: Callable | None = None) -> list:
    """Average M shard stacks of one round in byte-bounded kernel launches.

    ``shard_stacks`` is a sequence of stacks, each N rows of one shard —
    an (N, L_j) array, or a sequence of N row objects that ``read(row,
    start, stop)`` slices (default: ``row[start:stop]``) — every stack
    holding the same N clients in the same order. The stacks' columns
    are laid end to end and cut by :func:`fold_windows` so that each
    launch fits :func:`fold_budget_bytes`; each window is built on the
    host, moved to the device, folded and copied back before the next is
    built. The kernel returns sums, and the mean is one f32 divide on the
    host — the numpy evaluator's op — so an unweighted result is
    bit-identical to the streaming reference, and averaging being
    element-wise, to any other windowing.

    ``workers`` > 1 runs windows on the host fold pool — interpret mode
    only, where launches are host-bound; on TPU windows run one at a
    time so only one holds device memory.

    Returns a list of (L_j,) f32 means, one per input stack.
    """
    if interpret is None:
        interpret = _use_interpret()
    read = read or _slice_rows
    stacks = [np.asarray(s) if isinstance(s, jax.Array) else s
              for s in shard_stacks]
    if not stacks:
        return []
    n = len(stacks[0])
    assert all(len(s) == n for s in stacks), \
        "all shard stacks must hold the same N clients"
    lengths = [int(s[0].shape[0]) for s in stacks]
    offsets = np.cumsum([0] + lengths).tolist()
    if weights is None:
        w_dev, div = None, np.float32(float(n))
    else:
        w_host = np.asarray(weights, np.float32)
        w_dev, div = jnp.asarray(w_host), np.float32(w_host.sum())
    outs = [np.empty(l, np.float32) for l in lengths]
    from repro.core.fold_pool import get_pool
    pool = get_pool(workers)
    parts = pool.workers if interpret else 1
    windows = fold_windows(offsets[-1], n, fold_budget_bytes(), parts,
                           block_rows)

    def run(index: int, a: int, b: int) -> None:
        tile = block_rows * LANES
        cols = -(-(b - a) // tile) * tile
        with span("fold.window", index=index, n=n, cols=cols):
            with span("fold.fill", bytes=n * cols * 4):
                buf = np.empty((n, cols), np.float32)
                buf[:, b - a:] = 0.0
                segs = []
                for j, (off, l) in enumerate(zip(offsets, lengths)):
                    lo, hi = max(a, off), min(b, off + l)
                    if lo < hi:
                        segs.append((j, lo, hi))
                        for i, row in enumerate(stacks[j]):
                            buf[i, lo - a:hi - a] = read(row, lo - off,
                                                         hi - off)
            # The two waits below give fold.h2d and fold.kernel their
            # meaning. They cost at most one dispatch latency per window:
            # the kernel cannot start before its window has arrived, and
            # np.asarray blocks on the sum anyway.
            with span("fold.h2d", bytes=n * cols * 4):
                tiles = jax.device_put(buf.reshape(n, -1, LANES))
                tiles.block_until_ready()
            del buf
            with span("fold.kernel"):
                total = _fold_sum(tiles, w_dev, block_rows, interpret)
                total.block_until_ready()
            del tiles
            with span("fold.d2h", bytes=cols * 4):
                total = np.asarray(total).reshape(-1)
            with span("fold.divide"):
                for j, lo, hi in segs:
                    off = offsets[j]
                    np.divide(total[lo - a:hi - a], div,
                              out=outs[j][lo - off:hi - off])

    tasks = [(k, a, b) for k, (a, b) in enumerate(windows)]
    if interpret:
        pool.map(run, tasks)
    else:
        for task in tasks:
            run(*task)
    return outs


def fedavg_shards(client_shards, weights=None, block_rows: int = 32,
                  interpret: bool | None = None) -> np.ndarray:
    """client_shards: (N, L) flat shards -> (L,) f32 weighted mean."""
    return fedavg_multi([client_shards], weights, block_rows=block_rows,
                        interpret=interpret, workers=1)[0]


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _quant_flat(flat, block_rows, interpret):
    tiles, _ = _to_tiles(flat, block_rows)
    codes, scales = _q.quantize(tiles, block_rows=block_rows,
                                interpret=interpret)
    return codes, scales


def qsgd_compress(flat: jax.Array, block_rows: int = 32,
                  interpret: bool | None = None):
    """(L,) f32 -> (codes (R,128) int8, scales, L). ~4x smaller on the wire."""
    if interpret is None:
        interpret = _use_interpret()
    codes, scales = _quant_flat(flat, block_rows, interpret)
    return codes, scales, int(flat.shape[-1])


@partial(jax.jit, static_argnames=("l", "block_rows", "interpret"))
def _dequant_flat(codes, scales, l, block_rows, interpret):
    out = _q.dequantize(codes, scales, block_rows=block_rows,
                        interpret=interpret)
    return _from_tiles(out, l)


def qsgd_decompress(codes: jax.Array, scales: jax.Array, l: int,
                    block_rows: int = 32,
                    interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = _use_interpret()
    return _dequant_flat(codes, scales, l, block_rows, interpret)


@partial(jax.jit, static_argnames=("k_per_block", "block_rows", "interpret"))
def _topk_flat(flat, k_per_block, block_rows, interpret):
    tiles, l = _to_tiles(flat, block_rows)
    out = _tk.topk_sparsify(tiles, k_per_block, block_rows=block_rows,
                            interpret=interpret)
    return _from_tiles(out, l)


def topk_sparsify(flat: jax.Array, k_per_block: int, block_rows: int = 32,
                  interpret: bool | None = None) -> jax.Array:
    """Zero all but ~k_per_block largest-magnitude entries per tile."""
    if interpret is None:
        interpret = _use_interpret()
    return _topk_flat(flat, k_per_block, block_rows, interpret)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _rmsnorm(x2d, gamma, eps, block_rows, interpret):
    rows = x2d.shape[0]
    pad = (-rows) % block_rows
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    out = _rn.rmsnorm(x2d, gamma, eps=eps, block_rows=block_rows,
                      interpret=interpret)
    return out[:rows]


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5,
            block_rows: int = 8, interpret: bool | None = None) -> jax.Array:
    """x: (..., d) -> fused rmsnorm * gamma."""
    if interpret is None:
        interpret = _use_interpret()
    shape = x.shape
    out = _rmsnorm(x.reshape(-1, shape[-1]), gamma, eps, block_rows,
                   interpret)
    return out.reshape(shape)


@partial(jax.jit, static_argnames=("lr", "momentum", "block_rows",
                                   "interpret"), donate_argnums=(0, 2))
def _sgd_flat(p, g, v, lr, momentum, block_rows, interpret):
    pt, l = _to_tiles(p, block_rows)
    gt, _ = _to_tiles(g, block_rows)
    vt, _ = _to_tiles(v, block_rows)
    po, vo = _sgd.fused_sgd(pt, gt, vt, lr=lr, momentum=momentum,
                            block_rows=block_rows, interpret=interpret)
    return _from_tiles(po, l), _from_tiles(vo, l)


def sgd_momentum_update(params: jax.Array, grads: jax.Array,
                        velocity: jax.Array, lr: float,
                        momentum: float = 0.9, block_rows: int = 32,
                        interpret: bool | None = None):
    """Fused v ← μv+g; p ← p−ηv on a flat shard. Donates (p, v)."""
    if interpret is None:
        interpret = _use_interpret()
    return _sgd_flat(params, grads, velocity, lr, momentum, block_rows,
                     interpret)

"""jit'd public wrappers for the Pallas kernels.

Handle flat-vector ⇄ (rows, 128) tiling, padding to block multiples, and
interpret-mode selection (interpret=True off TPU — the kernel bodies
execute in Python for validation; on TPU they lower to Mosaic and never
run interpreted). :func:`kernel_mode` is the one switch between these
kernels and the numpy mirrors of the aggregation engine and wire codecs.
"""
from __future__ import annotations

import threading
import weakref
from concurrent.futures import ThreadPoolExecutor
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
from repro.launch.hostenv import host_timer
from repro.tracing import span, tally

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
# fold: byte-bounded launches of the fedavg_stream kernel, streamed through
# two reused host staging buffers per chip
# ---------------------------------------------------------------------------

#: Host bytes of one fold window's (N, cols) f32 input, and of each of the
#: two staging buffers per chip that the windows stream through. Chosen
#: from a sweep of 128 MB to 900 MB windows on a TPU v5e (PERF.md, section
#: 6).
STAGING_BYTES = 512 << 20

#: the staging buffers by slot (chip c owns slots 2c and 2c + 1), allocated
#: on first use and kept for the process
_staging: dict = {}
#: one streamed call at a time: every call shares the buffers and lanes
_staging_lock = threading.Lock()
#: the threads that run a streamed call's other lanes: each chip's odd
#: windows, and every chip's pipeline but the first
_lanes: ThreadPoolExecutor | None = None
_lanes_size = 0
#: the result buffers of :func:`fedavg_multi` calls that are still alive,
#: by id, until :func:`claim_result` hands one out
_results: weakref.WeakValueDictionary = weakref.WeakValueDictionary()


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _fold_sum(tiles, weights, block_rows, interpret):
    return _fa.fedavg_stream(tiles, weights, block_rows=block_rows,
                             interpret=interpret)


def fold_budget_bytes(device=None) -> int | None:
    """Bytes the fold's launches in flight may hold together on ``device``
    (default: the default device): half of what its allocator has free
    (``memory_stats()``), so they fit beside whatever earlier launches are
    still releasing. None where the backend reports no limit (the CPU)."""
    stats = (device or jax.devices()[0]).memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return (int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))) \
        // 2


def fold_windows(total: int, n: int, budget_bytes: int | None,
                 parts: int = 1, block_rows: int = 32) -> list:
    """Cut ``total`` fold columns of an N-client round into launch windows.

    Each window is ``[start, stop)`` with a tile-aligned start. Its (N,
    cols) f32 input, cols padded to the kernel tile, fits one staging
    buffer of :data:`STAGING_BYTES` (or is one tile, where N rows of a
    tile need more), and two launches in flight, each an (N, cols) input
    and a (cols,) output, fit ``budget_bytes`` (None = no device bound).
    Windows are as equal as the tiles allow and at least ``parts`` in
    number when there are enough tiles (the interpret-mode fold pool).
    """
    tile = block_rows * LANES
    n_tiles = -(-total // tile)
    if n_tiles == 0:
        return []
    cap = max(1, STAGING_BYTES // (4 * n * tile))
    if budget_bytes is not None:
        fits = budget_bytes // (2 * 4 * (n + 1) * tile)
        if fits < 1:
            raise ValueError(
                f"two {n}-client fold tiles in flight need "
                f"{2 * 4 * (n + 1) * tile} B, more than the device budget "
                f"of {budget_bytes} B")
        cap = min(cap, fits)
    n_win = max(-(-n_tiles // cap), min(parts, n_tiles))
    per = -(-n_tiles // n_win) * tile
    return [(s, min(s + per, total)) for s in range(0, total, per)]


def chip_ranges(total: int, chips: int, block_rows: int = 32) -> list:
    """Cut ``total`` fold columns into ``chips`` contiguous ``[start,
    stop)`` ranges, one per chip, each cut on a kernel-tile boundary.
    Every column holds the same N rows, so the ranges are balanced by
    bytes when their tiles are: they differ by at most one tile, the
    earlier chips taking the extra ones, and a chip gets an empty range
    only where there are fewer tiles than chips."""
    tile = block_rows * LANES
    base, extra = divmod(-(-total // tile), chips)
    cuts = [min((c * base + min(c, extra)) * tile, total)
            for c in range(chips + 1)]
    return list(zip(cuts, cuts[1:]))


def _staging_buffer(slot: int, elems: int) -> tuple[np.ndarray, int]:
    """Staging buffer ``slot`` with room for ``elems`` f32, and 1 if it
    was allocated now (first use, or a window larger than it)."""
    buf = _staging.get(slot)
    if buf is not None and buf.size >= elems:
        return buf, 0
    buf = _staging[slot] = np.empty(max(elems, STAGING_BYTES // 4),
                                    np.float32)
    return buf, 1


def _stage_lanes(size: int) -> ThreadPoolExecutor:
    """The lane threads, at least ``size`` of them; called under
    :data:`_staging_lock`, so a pool it replaces is idle."""
    global _lanes, _lanes_size
    if size > _lanes_size:
        if _lanes is not None:
            _lanes.shutdown(wait=False)
        _lanes = ThreadPoolExecutor(size, thread_name_prefix="repro-stage")
        _lanes_size = size
    return _lanes


def _overlapped(times: list) -> int:
    """The windows whose fill ran while an earlier window was in flight,
    from ``times``: per window [fill start, fill end, device_put, sum back
    on the host]."""
    return len([k for k, (fs, fe, _, _) in enumerate(times)
                if any(put < fe and fs < back for _, _, put, back in times[:k])])


def _slice_rows(x, s: int, e: int):
    return x[s:e]


def fedavg_multi(shard_stacks: Sequence, weights=None,
                 block_rows: int = 32,
                 interpret: bool | None = None,
                 workers: int | str | None = None,
                 read: Callable | None = None,
                 devices: Sequence | None = None) -> list:
    """Average M shard stacks of one round in byte-bounded kernel launches.

    ``shard_stacks`` is a sequence of stacks, each N rows of one shard —
    an (N, L_j) array, or a sequence of N row objects that ``read(row,
    start, stop)`` slices (default: ``row[start:stop]``) — every stack
    holding the same N clients in the same order. The stacks' columns
    are laid end to end, cut into one range per chip of ``devices``
    (:func:`chip_ranges`; None: the default device alone), and each range
    into windows by :func:`fold_windows`; each window is filled on the
    host, moved to its chip, folded, copied back and divided. The kernel
    returns sums, and the mean is one f32 divide on the host — the numpy
    evaluator's op — so an unweighted result is bit-identical to the
    streaming reference, and averaging being element-wise, to any other
    windowing on any number of chips.

    Each chip's windows stream through two host staging buffers of its
    own, kept for the process: the even windows through one, the odd
    through the other on a second thread. Window k+1 is filled, its
    column spans on the fold pool of ``workers``, while window k moves to
    the chip and folds. A chip's fills run in window order, and so do its
    transfers; a buffer is refilled only after the device array made from
    it is ready, so at most two windows are on a chip at once. The chips'
    pipelines run at the same time, the first on the calling thread. With
    ``workers`` > 1 in interpret mode, where launches are host-bound,
    windows instead run on the fold pool, each in fresh memory.

    Returns a list of (L_j,) f32 means, one per input stack: consecutive
    views, in stack order, of one newly allocated (sum of L_j,) buffer
    that the divide writes straight into (:func:`claim_result`).
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
    devices = list(devices) if devices else [None]
    if weights is None:
        w_devs, div = [None] * len(devices), np.float32(float(n))
    else:
        w_host = np.asarray(weights, np.float32)
        w_devs = [jax.device_put(w_host, d) for d in devices]
        div = np.float32(w_host.sum())
    flat = np.empty(offsets[-1], np.float32)
    _results[id(flat)] = flat
    outs = [flat[a:b] for a, b in zip(offsets, offsets[1:])]
    from repro.core.fold_pool import PARALLEL_MIN_ELEMS, get_pool, partition
    pool = get_pool(workers)
    fan_out = interpret and pool.workers > 1
    # (chip, start, stop) per window; a chip's windows are consecutive
    windows = []
    for c, (d, (lo, hi)) in enumerate(zip(
            devices, chip_ranges(offsets[-1], len(devices), block_rows))):
        windows += [(c, lo + a, lo + b) for a, b in fold_windows(
            hi - lo, n, fold_budget_bytes(d), pool.workers if fan_out else 1,
            block_rows)]
    tile = block_rows * LANES
    cols = [-(-(b - a) // tile) * tile for _, a, b in windows]
    per_chip = [[k for k, w in enumerate(windows) if w[0] == c]
                for c in range(len(devices))]
    # detlint: allow[ORD001] integer column counts — no float accumulation
    chip_bytes = [4 * n * sum(cols[k] for k in ks) for ks in per_chip]
    # a chip's fills run in window order, and so do its transfers: its
    # window k waits for its window before
    prev = [k - 1 if k and windows[k - 1][0] == w[0] else None
            for k, w in enumerate(windows)]
    filled = [threading.Event() for _ in windows]
    arrived = [threading.Event() for _ in windows]
    times = [[0.0] * 4 for _ in windows]

    def fill_cols(buf, a: int, lo: int, hi: int) -> None:
        """Round columns [a + lo, a + hi) into columns [lo, hi) of buf."""
        for off, l, stack in zip(offsets, lengths, stacks):
            s, e = max(a + lo, off), min(a + hi, off + l)
            if s < e:
                for i, row in enumerate(stack):
                    buf[i, s - a:e - a] = read(row, s - off, e - off)

    def fill_spans(width: int) -> list:
        if fan_out or n * width < PARALLEL_MIN_ELEMS:
            return [(0, width)]
        return partition(width, pool.workers, LANES)

    def run(k: int, buf) -> None:
        c, a, b = windows[k]
        p = prev[k]
        try:
            with span("fold.window", index=k, n=n, cols=cols[k], chip=c):
                if p is not None:
                    filled[p].wait()
                times[k][0] = host_timer()
                with span("fold.fill", bytes=n * cols[k] * 4, chip=c):
                    buf[:, b - a:] = 0.0
                    pool.map(partial(fill_cols, buf, a), fill_spans(b - a))
                times[k][1] = host_timer()
                filled[k].set()
                if p is not None:
                    arrived[p].wait()
                times[k][2] = host_timer()
                # The waits below give fold.h2d and fold.kernel their
                # meaning, and the first frees the buffer for its next
                # fill: whatever host-buffer semantics device_put has, a
                # ready array no longer reads its host source.
                with span("fold.h2d", bytes=n * cols[k] * 4, chip=c):
                    tiles = jax.device_put(buf.reshape(n, -1, LANES),
                                           devices[c])
                    tiles.block_until_ready()
                arrived[k].set()
                with span("fold.kernel", chip=c):
                    total = _fold_sum(tiles, w_devs[c], block_rows, interpret)
                    total.block_until_ready()
                del tiles
                with span("fold.d2h", bytes=cols[k] * 4, chip=c):
                    total = np.asarray(total).reshape(-1)
                times[k][3] = host_timer()
                with span("fold.divide", chip=c):
                    for off, l, out in zip(offsets, lengths, outs):
                        lo, hi = max(a, off), min(b, off + l)
                        if lo < hi:
                            np.divide(total[lo - a:hi - a], div,
                                      out=out[lo - off:hi - off])
        except BaseException:
            for ev in filled + arrived:      # let no other window wait
                ev.set()
            raise

    def fresh(k: int) -> None:
        run(k, np.empty((n, cols[k]), np.float32))

    def lane(ks: list, slot: int) -> int:
        """Windows ``ks`` through staging buffer ``slot``; returns the
        buffers it allocated."""
        if not ks:
            return 0
        staging, allocs = _staging_buffer(slot, n * max(cols[k] for k in ks))
        for k in ks:
            run(k, staging[:n * cols[k]].reshape(n, cols[k]))
        return allocs

    def chip(c: int) -> tuple[int, int]:
        """Chip ``c``'s pipeline; returns (buffers allocated, windows
        overlapped)."""
        ks = per_chip[c]
        with tally("fold.chip", chip=c, windows=len(ks), bytes=chip_bytes[c],
                   overlapped=0) as counts:
            if fan_out:
                pool.map(fresh, [(k,) for k in ks])
                allocs = len(ks)
            else:
                other = (lanes.submit(lane, ks[1::2], 2 * c + 1)
                         if len(ks) > 1 else None)
                allocs = 0
                try:
                    allocs = lane(ks[0::2], 2 * c)
                finally:
                    # the second lane leaves its buffer before the lock goes
                    allocs += other.result() if other else 0
            counts["overlapped"] = _overlapped([times[k] for k in ks])
        return allocs, counts["overlapped"]

    with tally("fold.stream", windows=len(windows), chips=len(devices),
               max_chip_bytes=max(chip_bytes), min_chip_bytes=min(chip_bytes),
               staging_bytes=0, allocs=0, overlapped=0) as counts:
        if windows:
            with _staging_lock:
                lanes = _stage_lanes(2 * len(devices) - 1)
                others = [lanes.submit(chip, c)
                          for c in range(1, len(devices))]
                done = []
                try:
                    done.append(chip(0))
                finally:
                    done += [f.result() for f in others]
                counts["allocs"], counts["overlapped"] = map(sum, zip(*done))
                if not fan_out:
                    counts["staging_bytes"] = _staging[0].nbytes
    return outs


def claim_result(buf) -> bool:
    """Whether ``buf`` is the buffer one :func:`fedavg_multi` call
    allocated for its means and no one has claimed yet; it is claimed now.
    So a result buffer is handed out once, and no array a caller made
    itself is ever taken for one."""
    return _results.pop(id(buf), None) is buf


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

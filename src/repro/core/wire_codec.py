"""Pluggable on-the-wire representation of a client contribution.

The paper's §VI future work ("composition with gradient compression to
reduce S3 transfer volume") made the wire format a per-benchmark hack:
every layer of the stack assumed a contribution is raw f32 shard bytes.
This module makes the representation a first-class axis — a
:class:`WireCodec` declares

  * ``encode(shard) -> WirePayload`` — what a client PUTs,
  * ``decode(payload) -> np.ndarray`` — what an aggregator folds
    (decode-before-fold; the chunked engines use :meth:`decode_range`
    so the decode fuses into the cache-resident fold),
  * ``wire_bytes(nbytes)`` — the *modeled* on-the-wire size of a raw
    f32 object of ``nbytes`` (a pure function, shared verbatim by the
    simulator's upload schedule and the analytical cost model — which is
    what keeps event-sim / cost-model parity to float epsilon), and
  * ``decode_cost_s(nbytes)`` — modeled per-contribution decode CPU time.

Codecs register through :func:`register_codec`, mirroring the topology
registry; resolution follows the same knob discipline as engines and
schedules (``SessionConfig.codec`` / ``aggregate_round(codec=)`` / env
``REPRO_AGG_CODEC``, default ``"identity"``).

Builtins:

  * ``identity`` — the raw f32 passthrough. Bit-identical **by
    construction**: ``encode`` returns its input object unchanged (zero-
    copy shard views survive), nothing in the round path can observe the
    codec at all, so the entire pre-codec invariant grid holds unmodified.
  * ``fp16`` — half-precision truncation, 2× smaller.
  * ``qsgd8`` — per-tile symmetric int8 quantization (deterministic
    round-to-nearest, the Pallas ``kernels/quantize.py`` scheme), ~4×
    smaller. The numpy mirror replays the kernel's f32 op sequence up to
    the scale divide (see :class:`Qsgd8Codec`); on TPU hosts (or
    ``REPRO_AGG_PALLAS=1``) encoding dispatches to the Pallas kernel.
  * ``topk`` — per-tile magnitude top-k sparsification (the Pallas
    ``kernels/topk_sparsify.py`` bisection), shipped as a sparse
    index+value payload with a fixed per-tile budget.

Lossy codecs are still **deterministic**: encode/decode are pure
functions of the input bytes, so ``avg_flat`` remains bit-identical
across engines, schedules, read-ahead windows and arrival permutations —
only the identity codec additionally guarantees bit-identity to the
*uncompressed* reference (see ``core/aggregation.py``).
"""
from __future__ import annotations

import math

import numpy as np

from repro import knobs
from repro.config import AGG_COMPUTE_BPS

LANES = 128
BLOCK_ROWS = 32
TILE = BLOCK_ROWS * LANES            # elements per codec tile (matches the
                                     # Pallas kernels' default block)
QMAX = np.float32(127.0)
BISECT_ITERS = 24                    # kernels/topk_sparsify.py


# ---------------------------------------------------------------------------
# Payload
# ---------------------------------------------------------------------------

class WirePayload:
    """One encoded contribution as stored / transferred.

    ``nbytes`` is the codec's *declared* wire size (``codec.wire_bytes`` of
    the raw f32 size) — the store's op log, the runtime's GET latency and
    the memory accounting all read it, so every layer of the simulation
    sees the reduced transfer volume without knowing the codec exists.
    ``parts`` holds the in-memory representation (codes/scales/indices…);
    its exact numpy layout is a simulation artifact, not the wire format.
    ``codec_obj`` is the encoding codec *instance* — decode always goes
    back through the object that produced the payload, so an unregistered
    ``WireCodec`` instance passed as the knob round-trips correctly and a
    name collision with a registered codec can never mis-decode.
    """

    __slots__ = ("codec_obj", "parts", "n_elems", "raw_nbytes",
                 "_wire_nbytes")

    def __init__(self, codec_obj: "WireCodec", parts: dict, n_elems: int,
                 raw_nbytes: int, wire_nbytes: int):
        self.codec_obj = codec_obj
        self.parts = parts
        self.n_elems = int(n_elems)
        self.raw_nbytes = int(raw_nbytes)
        self._wire_nbytes = int(wire_nbytes)

    @property
    def codec(self) -> str:
        return self.codec_obj.name

    @property
    def nbytes(self) -> int:
        return self._wire_nbytes

    @property
    def shape(self) -> tuple:
        return (self.n_elems,)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"WirePayload(codec={self.codec!r}, elems={self.n_elems}, "
                f"wire={self._wire_nbytes}B of raw {self.raw_nbytes}B)")


class EncodedView:
    """Lazy decoded view of a :class:`WirePayload` (batched engine).

    Presents the payload as a logical f32 vector whose chunks decode on
    demand (:meth:`read`), so the deferred DAG evaluator fuses the decode
    into its cache-resident fold instead of materializing every decoded
    contribution up front. ``read(s, e)`` is bitwise
    ``decode(payload)[s:e]`` — chunking never moves arithmetic.
    """

    __slots__ = ("codec_obj", "payload", "_mat")

    dtype = np.dtype(np.float32)

    def __init__(self, codec_obj: "WireCodec", payload: WirePayload):
        self.codec_obj = codec_obj
        self.payload = payload
        self._mat: np.ndarray | None = None

    @property
    def size(self) -> int:
        return self.payload.n_elems

    @property
    def shape(self) -> tuple:
        return (self.payload.n_elems,)

    @property
    def nbytes(self) -> int:
        return self.payload.n_elems * 4       # the *decoded* f32 size

    def read(self, start: int, stop: int) -> np.ndarray:
        if self._mat is not None:
            return self._mat[start:stop]
        return self.codec_obj.decode_range(self.payload, start, stop)

    def materialize(self) -> np.ndarray:
        if self._mat is None:
            self._mat = self.codec_obj.decode(self.payload)
        return self._mat


def _as_f32(shard) -> np.ndarray:
    """Encoder input normalization: ndarray or zero-copy ShardView."""
    if hasattr(shard, "materialize") and not isinstance(shard, np.ndarray):
        shard = shard.materialize()
    return np.asarray(shard, np.float32)


def _tiles_of(n_elems: int) -> int:
    return math.ceil(n_elems / TILE)


def _pad_tiles(flat: np.ndarray) -> np.ndarray:
    """(L,) -> (n_tiles, TILE) zero-padded — the kernels' tiling."""
    n = flat.shape[0]
    nt = _tiles_of(n)
    if nt * TILE != n:
        flat = np.pad(flat, (0, nt * TILE - n))
    return flat.reshape(nt, TILE)


def _use_kernels() -> bool:
    """Encode with the Pallas kernels where :func:`repro.kernels.ops
    .kernel_mode` says they run (TPU hosts, or forced via
    ``REPRO_AGG_PALLAS``); the numpy mirrors are far faster than
    interpret mode on CPUs."""
    from repro.kernels import ops as kops
    return kops.kernel_mode() is not None


def qsgd8_numpy(flat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Numpy mirror of ``kernels/quantize.py``: (L,) f32 -> (int8 codes
    (L,), f32 scales (tiles,)), replaying the kernel's f32 op sequence
    (amax → scale = amax/127 → clip(rint(x/scale)))."""
    tiles = _pad_tiles(flat)
    amax = np.abs(tiles).max(axis=1)
    scales = np.where(amax > 0, amax / QMAX,
                      np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(tiles / scales[:, None]), -QMAX, QMAX)
    return q.astype(np.int8).reshape(-1)[:flat.shape[0]], scales


def qsgd8_kernel(flat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """:func:`qsgd8_numpy`'s contract through the Pallas kernel."""
    from repro.kernels import ops as kops
    codes, scales, _ = kops.qsgd_compress(flat, block_rows=BLOCK_ROWS)
    return (np.asarray(codes).reshape(-1)[:flat.shape[0]],
            np.asarray(scales).reshape(-1))


def topk_numpy(flat: np.ndarray, k_per_block: int) -> np.ndarray:
    """Numpy mirror of ``kernels/topk_sparsify.py``: the dense (L,) f32
    vector with all but each tile's bisection-threshold top-k zeroed."""
    tiles = _pad_tiles(flat)
    ax = np.abs(tiles)
    lo = np.zeros(tiles.shape[0], np.float32)
    hi = ax.max(axis=1) + np.float32(1e-12)
    half = np.float32(0.5)
    for _ in range(BISECT_ITERS):
        mid = half * (lo + hi)
        keep = (ax >= mid[:, None]).sum(axis=1) >= k_per_block
        lo = np.where(keep, mid, lo)
        hi = np.where(keep, hi, mid)
    dense = np.where(ax >= lo[:, None], tiles, np.float32(0.0))
    return dense.reshape(-1)[:flat.shape[0]]


def topk_kernel(flat: np.ndarray, k_per_block: int) -> np.ndarray:
    """:func:`topk_numpy`'s contract through the Pallas kernel."""
    from repro.kernels import ops as kops
    return np.asarray(kops.topk_sparsify(flat, k_per_block,
                                         block_rows=BLOCK_ROWS))


# ---------------------------------------------------------------------------
# Codec interface + registry
# ---------------------------------------------------------------------------

class WireCodec:
    """Strategy interface for the on-the-wire contribution format."""

    name = "?"
    #: True when decode(encode(x)) == x bit-for-bit for every f32 input
    lossless = False

    # -- data plane ----------------------------------------------------------
    def encode(self, shard):
        """Shard (ndarray or zero-copy view) -> what the client PUTs."""
        raise NotImplementedError

    def decode(self, payload: WirePayload) -> np.ndarray:
        """Payload -> the f32 vector the aggregator folds."""
        raise NotImplementedError

    def decode_range(self, payload: WirePayload, start: int,
                     stop: int) -> np.ndarray:
        """Bitwise ``decode(payload)[start:stop]`` without materializing
        the rest — the fused chunked-fold entry point. The default decodes
        fully; codecs override with a real ranged decode."""
        return self.decode(payload)[start:stop]

    # -- modeled platform terms ---------------------------------------------
    def wire_bytes(self, nbytes: int) -> int:
        """Declared wire size of a raw f32 object of ``nbytes``. Pure
        function — the upload schedule, the stored payload's ``nbytes``
        and the analytical cost model all use this one definition."""
        raise NotImplementedError

    def decode_cost_s(self, nbytes: int) -> float:
        """Modeled CPU seconds to decode one contribution of raw size
        ``nbytes`` (charged inside the aggregator invocation)."""
        return nbytes / AGG_COMPUTE_BPS

    # -- helpers -------------------------------------------------------------
    def _payload(self, parts: dict, n_elems: int) -> WirePayload:
        raw = n_elems * 4
        return WirePayload(self, parts, n_elems, raw,
                           self.wire_bytes(raw))


_REGISTRY: dict[str, WireCodec] = {}


def register_codec(name: str, *, replace: bool = False):
    """Class decorator: register a :class:`WireCodec` under ``name`` —
    the same public extension discipline as ``@register_topology``."""

    def deco(cls):
        if not replace and name in _REGISTRY:
            raise ValueError(
                f"codec {name!r} is already registered "
                f"({type(_REGISTRY[name]).__name__}); pass replace=True "
                f"to override")
        instance = cls() if isinstance(cls, type) else cls
        instance.name = name
        _REGISTRY[name] = instance
        return cls

    return deco


DEFAULT_CODEC = "identity"


def get_codec(codec: str | WireCodec | None = None) -> WireCodec:
    """Resolve the codec knob: an instance, a name, or ``None``/"auto"
    (env ``REPRO_AGG_CODEC``, else ``"identity"``)."""
    if isinstance(codec, WireCodec):
        return codec
    if codec is None or codec == "auto":
        codec = knobs.env_codec(DEFAULT_CODEC)
    try:
        return _REGISTRY[codec]
    except KeyError:
        raise ValueError(
            f"unknown wire codec {codec!r} (registered: "
            f"{sorted(_REGISTRY)})") from None


def available_codecs() -> tuple:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Builtins
# ---------------------------------------------------------------------------

@register_codec("identity")
class IdentityCodec(WireCodec):
    """Raw f32 passthrough — the pre-codec wire format, bit-identical by
    construction: ``encode`` returns the input object itself (zero-copy
    shard views included), so nothing downstream can tell the codec layer
    exists."""

    lossless = True

    def encode(self, shard):
        return shard

    def decode(self, payload):
        raise TypeError("identity contributions are stored raw — there is "
                        "no payload to decode")

    def wire_bytes(self, nbytes: int) -> int:
        return int(nbytes)

    def decode_cost_s(self, nbytes: int) -> float:
        return 0.0


@register_codec("fp16")
class Fp16Codec(WireCodec):
    """Half-precision truncation: 2× smaller, ~3 decimal digits kept."""

    def encode(self, shard):
        flat = _as_f32(shard)
        return self._payload({"half": flat.astype(np.float16)},
                             flat.shape[0])

    def decode(self, payload):
        return payload.parts["half"].astype(np.float32)

    def decode_range(self, payload, start, stop):
        return payload.parts["half"][start:stop].astype(np.float32)

    def wire_bytes(self, nbytes: int) -> int:
        return (int(nbytes) // 4) * 2


@register_codec("qsgd8")
class Qsgd8Codec(WireCodec):
    """Deterministic QSGD: per-``TILE`` symmetric int8 round-to-nearest
    with one f32 scale per tile (``kernels/quantize.py``). ~4× smaller.

    The numpy mirror replays the kernel's f32 op sequence (amax → scale
    = amax/127 → clip(rint(x/scale))), but the kernel's ``amax / 127``
    is not numpy's correctly rounded divide: on a TPU v5e 1,710 of
    32,792 tile scales of one VGG-16 gradient differed in the last bit
    (moving 115 of its 134M codes), and interpret mode differs the same
    way. So a payload depends on which path encoded it; every engine of
    one host folds the same payloads, which keeps ``avg_flat``
    bit-identical across engines.
    """

    def encode(self, shard):
        flat = _as_f32(shard)
        n = flat.shape[0]
        if n == 0:
            return self._payload({"codes": np.empty(0, np.int8),
                                  "scales": np.empty(0, np.float32)}, 0)
        encode = qsgd8_kernel if _use_kernels() else qsgd8_numpy
        codes, scales = encode(flat)
        return self._payload({"codes": codes, "scales": scales}, n)

    def decode(self, payload):
        return self.decode_range(payload, 0, payload.n_elems)

    def decode_range(self, payload, start, stop):
        codes = payload.parts["codes"][start:stop]
        if codes.size == 0:
            return np.empty(0, np.float32)
        lo_tile = start // TILE
        hi_tile = (stop - 1) // TILE + 1
        rep = np.repeat(payload.parts["scales"][lo_tile:hi_tile], TILE)
        off = start - lo_tile * TILE
        return codes.astype(np.float32) * rep[off:off + codes.shape[0]]

    def wire_bytes(self, nbytes: int) -> int:
        elems = int(nbytes) // 4
        return elems + 4 * _tiles_of(elems)    # int8/elem + f32 scale/tile


@register_codec("topk")
class TopkCodec(WireCodec):
    """Per-tile magnitude top-k sparsification shipped sparse.

    The keep-mask is the Pallas ``kernels/topk_sparsify.py`` bisection
    threshold (block-local relaxation of global top-k; ties at the
    threshold may keep slightly more than k). The payload carries
    (int32 index, f32 value) pairs; the declared wire size is the fixed
    per-tile budget ``k_per_block · 8`` bytes — a pure function of the
    raw size, which is what the cost model needs.
    """

    k_per_block = 128                 # of TILE=4096: 32× fewer survivors,
                                      # 16× fewer bytes at 8 B/survivor

    def _sparsify(self, flat: np.ndarray) -> np.ndarray:
        """Dense tile-local top-k mask application (kernel semantics)."""
        sparsify = topk_kernel if _use_kernels() else topk_numpy
        return sparsify(flat, self.k_per_block)

    def encode(self, shard):
        flat = _as_f32(shard)
        n = flat.shape[0]
        if n == 0:
            return self._payload({"idx": np.empty(0, np.int32),
                                  "val": np.empty(0, np.float32)}, 0)
        dense = self._sparsify(flat)
        idx = np.flatnonzero(dense).astype(np.int32)
        return self._payload({"idx": idx,
                              "val": dense[idx].astype(np.float32)}, n)

    def decode(self, payload):
        out = np.zeros(payload.n_elems, np.float32)
        out[payload.parts["idx"]] = payload.parts["val"]
        return out

    def decode_range(self, payload, start, stop):
        idx = payload.parts["idx"]
        lo = int(np.searchsorted(idx, start, side="left"))
        hi = int(np.searchsorted(idx, stop, side="left"))
        out = np.zeros(stop - start, np.float32)
        out[idx[lo:hi] - start] = payload.parts["val"][lo:hi]
        return out

    def wire_bytes(self, nbytes: int) -> int:
        elems = int(nbytes) // 4
        return _tiles_of(elems) * self.k_per_block * 8


# ---------------------------------------------------------------------------
# Decode plumbing shared by the engines and the round driver
# ---------------------------------------------------------------------------

def is_encoded(value) -> bool:
    return isinstance(value, WirePayload)


def decode_eager(payload: WirePayload) -> np.ndarray:
    """Decode a payload with its own codec (streaming/incremental path)."""
    return payload.codec_obj.decode(payload)


def decode_lazy(payload: WirePayload) -> EncodedView:
    """Chunk-decodable view of a payload (batched engine path)."""
    return EncodedView(payload.codec_obj, payload)

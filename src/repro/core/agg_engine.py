"""Pluggable aggregation execution engines.

The simulated-Lambda aggregation path has two concerns that this module
separates:

  * **modeled platform accounting** — S3 op counts, transfer/compute time,
    billed GB-s, peak memory. Always per-invocation, always identical.
  * **actual arithmetic** — the real numpy averaging whose result feeds the
    bit-identity checks and the training loop.

Three backends implement the same primitive-op protocol:

  * ``"streaming"`` — the reference. Arithmetic runs inline inside each
    simulated invocation, one contribution at a time (the paper's two-buffer
    aggregator). This is the seed implementation, byte for byte.
  * ``"batched"`` — the fast path. Invocation bodies run with *lazy handles*
    (size-typed placeholders); at round end the recorded DAG of averages is
    evaluated in one chunked, cache-resident pass that keeps accumulators in
    L2-sized blocks, fuses all phases of a topology per chunk (tree partials
    never round-trip through DRAM), threads across disjoint element ranges,
    and — when a TPU is present (or ``REPRO_AGG_PALLAS=1``) — dispatches
    unweighted shard averages to the Pallas ``fedavg_multi`` kernel.
  * ``"incremental"`` — the streaming *prefix fold*, tuned. Arithmetic is
    eager like ``streaming`` (the running prefix mean is up to date the
    moment contribution *i* lands — the natural partner of the pipelined
    round schedule, where aggregators fold each contribution on arrival),
    but folds in cache-resident chunks with preallocated accumulators, so
    the weighted path never allocates the streaming reference's two
    full-size f64 temporaries per contribution. Chunking is element-wise,
    so the IEEE op sequence per element is exactly the streaming
    reference's — ``avg_flat`` stays bit-identical.
  * ``"host_mesh"`` — the batched DAG with its unweighted folds dispatched
    through ``shard_map`` over a 1-D mesh of host CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``), each device
    folding a contiguous element shard in the reference op order; the
    final divide runs on the host, so bits are preserved.

Host parallelism: the batched/host_mesh evaluators split disjoint
element ranges across a :class:`~repro.core.fold_pool.ParallelFoldPool`
sized by the ``workers`` knob (``SessionConfig.workers`` /
``REPRO_AGG_WORKERS``, default = real cores). The partitioning is
chunk-aligned and element-wise, so ``avg_flat`` is bit-identical at
every worker count — parallelism moves wall-clock, never bits.

Both backends drive the **same invocation body template**, so every
accounting field (``puts``/``gets``, ``billed_gb_s``, ``peak_memory_mb``,
``duration_s``, phase walls) is identical by construction. The batched
numpy evaluator replays the exact per-element IEEE operation sequence of
the streaming reference (left-fold accumulate, single divide, f32 cast), so
``avg_flat`` is **bit-identical** — the paper's invariance-by-construction
property, enforced in ``tests/test_agg_engine.py``.

The Pallas path shares the accumulation order and returns sums; the
single divide runs on the host with the evaluator's f32 op (one divide
rule on every backend), so it is bit-identical too. In interpret mode
(non-TPU hosts) it is far slower than the numpy evaluator — hence
:func:`repro.kernels.ops.kernel_mode` turns it on by itself only on TPU
backends.

Selection: pass ``engine="streaming" | "batched" | "incremental" |
"host_mesh"`` to ``aggregate_round`` (or any topology function), or set
``REPRO_AGG_ENGINE`` in the environment; the default is ``"batched"``. Engines compose freely
with the round *schedule* knob (``schedule="barrier" | "pipelined"`` /
``REPRO_AGG_SCHEDULE``): accounting is value-agnostic, so every engine
yields identical modeled platform numbers under either schedule.

**Wire codecs (decode-before-fold contract).** When a round runs with a
non-identity :mod:`~repro.core.wire_codec` (``SessionConfig.codec`` /
``REPRO_AGG_CODEC``), client contributions arrive as encoded
``WirePayload`` objects. The shared body template buffers the *encoded*
bytes (GETs, stalls and the read-ahead window's memory all see the
reduced wire size) and decodes each contribution exactly once, at the
fold frontier, before folding it — charging the codec's declared decode
cost. Every engine observes the same decoded f32 values in the same
order, so ``avg_flat`` stays **bit-identical across engines, schedules
and readahead_k for a fixed codec** (lossy codecs are deterministic);
only ``codec="identity"`` additionally guarantees bit-identity to the
uncompressed reference — with it the codec layer is byte-for-byte
invisible.

**Fault-tolerant rounds (subset folds).** Dropout, partial participation,
deadlines and the quorum schedule (:mod:`repro.serverless.faults`) are
handled entirely at the round-driver level: the driver builds the
aggregation program over the *surviving* membership, so engines see an
ordinary N'-client round — group sizes, weights and the divide-by-N'
normalization all follow from the program, and no engine carries
fault-awareness. Consequently a faulty round's ``avg_flat`` equals the
plain mean over the survivors' gradients and remains bit-identical
across engines for a fixed survivor set and fold order.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro import knobs
from repro.core.fold_pool import PARALLEL_MIN_ELEMS  # noqa: F401  (re-export)
from repro.core.fold_pool import CHUNK_ELEMS, ParallelFoldPool, get_pool
from repro.core.sharding import PartitionPlan, ShardView, shard, shard_views
from repro.core.wire_codec import (EncodedView, WirePayload, decode_eager,
                                   decode_lazy)
from repro.serverless.event_sim import ReadAheadWindow
from repro.store import ObjectStore
from repro.tracing import span


# ---------------------------------------------------------------------------
# Lazy values
# ---------------------------------------------------------------------------

def _size_of(x) -> int:
    return int(x.shape[0])


def _chunk_of(x, s: int, e: int) -> np.ndarray:
    """Chunk [s, e) of an input: ndarray slice, ShardView gather, or a lazy
    node's already-evaluated output slice."""
    if isinstance(x, LazyAverage):
        return x.out[s:e]
    if isinstance(x, (ShardView, EncodedView)):
        return x.read(s, e)
    return x[s:e]


class _PendingAcc:
    """Accumulator under construction inside a deferred invocation body.

    Only its byte size matters to the runtime: f64 while accumulating a
    weighted mean (matching the streaming reference's float64 running sum),
    f32 otherwise.
    """

    __slots__ = ("inputs", "weighted", "size")

    def __init__(self, first, weighted: bool):
        self.inputs = [first]
        self.weighted = weighted
        self.size = _size_of(first)

    @property
    def nbytes(self) -> int:
        return (8 if self.weighted else 4) * self.size


class LazyAverage:
    """Deferred (weighted) streaming mean of its inputs.

    Inputs are ndarrays, :class:`ShardView` s, or other ``LazyAverage``
    nodes (tree topologies) — the captured objects themselves, so
    materialization never re-reads the object store. ``out`` is filled by
    the chunked DAG evaluator; until then the handle stands in for the f32
    result array in the store (same ``nbytes``/``shape``/``dtype``).
    """

    __slots__ = ("inputs", "weights", "size", "out")

    dtype = np.dtype(np.float32)

    def __init__(self, inputs: list, weights: list[float] | None):
        self.inputs = inputs
        self.weights = weights
        self.size = _size_of(inputs[0]) if inputs else 0
        self.out: np.ndarray | None = None

    @property
    def shape(self) -> tuple:
        return (self.size,)

    @property
    def nbytes(self) -> int:
        return 4 * self.size

    def _ancestors(self) -> list["LazyAverage"]:
        seen, order = set(), []

        def visit(node):
            if id(node) in seen:
                return
            seen.add(id(node))
            for x in node.inputs:
                if isinstance(x, LazyAverage) and x.out is None:
                    visit(x)
            order.append(node)

        visit(self)
        return order

    def materialize(self) -> np.ndarray:
        if self.out is None:
            _evaluate_nodes(self._ancestors())
        return self.out


def _materialize(x):
    if isinstance(x, np.ndarray):
        return x
    if hasattr(x, "materialize"):
        return x.materialize()
    return x


# ---------------------------------------------------------------------------
# Chunked DAG evaluator (bit-identical to the streaming reference)
# ---------------------------------------------------------------------------

class _Scratch:
    """Per-worker fold buffers, reused across chunks and nodes."""

    __slots__ = ("acc32", "acc64", "buf64")

    def __init__(self, chunk: int):
        self.acc32 = np.empty(chunk, np.float32)
        self.acc64 = np.empty(chunk, np.float64)
        self.buf64 = np.empty(chunk, np.float64)


def _node_chunk(nd: LazyAverage, s: int, e: int, scr: _Scratch) -> None:
    """Evaluate node ``nd`` over elements [s, e).

    Replays the exact IEEE op sequence of :class:`StreamingBackend`:
    unweighted — f32 left-fold then one f32 divide; weighted — f64
    ``x_i * w_i`` left-fold, one f64 divide by ``float(sum(w))``, f32 cast.
    """
    m = e - s
    ins = nd.inputs
    if nd.weights is None:
        acc = scr.acc32[:m]
        np.copyto(acc, _chunk_of(ins[0], s, e))
        for x in ins[1:]:
            np.add(acc, _chunk_of(x, s, e), out=acc)
        np.divide(acc, np.float32(float(len(ins))), out=nd.out[s:e])
    else:
        # dtype=np.float64 forces the f64 ufunc loop (cast-then-multiply in
        # one buffered pass) on every numpy scalar-promotion regime — the
        # streaming reference's ``arr.astype(np.float64) * w``. A weight of
        # exactly 1.0 scales exactly, so the multiply is skipped and the
        # cast fuses into the accumulate.
        acc, buf = scr.acc64[:m], scr.buf64[:m]
        w = nd.weights
        if w[0] == 1.0:
            np.copyto(acc, _chunk_of(ins[0], s, e))
        else:
            np.multiply(_chunk_of(ins[0], s, e), w[0], out=acc,
                        dtype=np.float64)
        for i in range(1, len(ins)):
            if w[i] == 1.0:
                np.add(acc, _chunk_of(ins[i], s, e), out=acc,
                       dtype=np.float64)
            else:
                np.multiply(_chunk_of(ins[i], s, e), w[i], out=buf,
                            dtype=np.float64)
                np.add(acc, buf, out=acc)
        np.divide(acc, float(sum(w)), out=buf)
        nd.out[s:e] = buf          # f64 -> f32 cast, same as astype


def _evaluate_nodes(nodes: Sequence[LazyAverage],
                    chunk: int = CHUNK_ELEMS,
                    pool: ParallelFoldPool | None = None) -> None:
    """Fill ``out`` for every pending node.

    Nodes are grouped by element count; within a group they are kept in
    creation (= phase/topological) order and evaluated chunk-by-chunk, all
    nodes per chunk, so a tree's level-2 fold reads its level-1 partials
    while those chunks are still cache-hot, and partials hit DRAM exactly
    once (their final f32 write). Disjoint element ranges go to the
    :class:`~repro.core.fold_pool.ParallelFoldPool`'s workers; chunking
    is element-wise so the result is bit-identical regardless of chunk
    size or worker count.
    """
    pending = [nd for nd in nodes if nd.out is None]
    if not pending:
        return
    if pool is None:
        pool = get_pool()
    groups: dict[int, list[LazyAverage]] = {}
    for nd in pending:
        nd.out = np.empty(nd.size, np.float32)
        groups.setdefault(nd.size, []).append(nd)

    # detlint: allow[ORD001] groups is insertion-ordered by node creation
    # (= phase/topological) order — that IS the canonical fold order
    for size, group in groups.items():
        if size == 0:
            continue

        def run(lo: int, hi: int, group=group):
            scr = _Scratch(chunk)
            for s in range(lo, hi, chunk):
                e = min(s + chunk, hi)
                for nd in group:
                    _node_chunk(nd, s, e, scr)

        pool.run_spans(run, size, chunk)


# ---------------------------------------------------------------------------
# Invocation body templates (shared by both backends)
# ---------------------------------------------------------------------------

def _avg_body(backend: "ExecutionBackend", store: ObjectStore,
              in_keys: Sequence[str], out_key: str,
              weights: Sequence[float] | None = None,
              readahead_k: int = 1):
    """Streaming fold with a bounded out-of-order read-ahead window.

    The fold itself is **strictly in in_keys (client-index) order** — the
    bit-reproducibility contract — but the body may GET up to
    ``readahead_k`` contributions at-or-ahead of the fold frontier into a
    bounded buffer (:class:`~repro.serverless.event_sim.ReadAheadWindow`),
    so under the pipelined schedule a late low-index upload no longer
    blocks every later read. ``readahead_k=1`` is byte-for-byte the legacy
    one-at-a-time loop (fetch order == index order, 2-buffer bound); under
    the barrier schedule every key is available at time 0, so any ``k``
    degenerates to index order too.

    The ctx models peak memory ``(k+1)``·input + overhead: running sum +
    up to ``k`` buffered inputs (incl. the transient deserialization copy
    of the in-flight GET) — the paper's 3×input+450 MB formula at
    ``k<=2``. The backend supplies the arithmetic (inline numpy or lazy
    handles); the ctx call sequence is identical across backends.

    **Decode-before-fold.** When a fetched value is a
    :class:`~repro.core.wire_codec.WirePayload` (a lossy wire codec is
    active), the body buffers the *encoded* payload — GET latency,
    transfer time and the prefetch window's memory all see the reduced
    wire size — and decodes it the moment it reaches the fold frontier:
    the codec's declared ``decode_cost_s`` is charged, the decoded f32
    buffer is allocated, the wire buffer freed, and the fold proceeds on
    decoded values exactly as before. ``backend.decode_value`` picks the
    arithmetic: an eager numpy decode (streaming/incremental) or a lazy
    chunk-decoding view (batched — the decode fuses into the chunked DAG
    evaluation, bitwise identical to the eager decode). Under the
    ``identity`` codec no payload ever appears and this path is
    byte-for-byte the pre-codec loop.
    """
    def body(ctx):
        acc = None
        n = len(in_keys)
        win = ReadAheadWindow([ctx.avail_time(k) for k in in_keys],
                              readahead_k)
        buffered: dict = {}
        while not win.done:
            if win.foldable:
                i = win.frontier
                arr = buffered.pop(i)
                if isinstance(arr, WirePayload):
                    # decode through the instance that encoded the payload
                    # (unregistered codec objects round-trip; a registered
                    # name collision cannot mis-decode)
                    codec = arr.codec_obj
                    ctx.work(codec.decode_cost_s(arr.raw_nbytes))
                    ctx.free(arr.nbytes)              # wire buffer released
                    arr = backend.decode_value(codec, arr)
                    ctx.alloc(backend.nbytes(arr))    # decoded f32 buffer
                    # (chunk-fused in the batched engine, so the peak
                    # stays within the (k+1)-input envelope)
                if acc is None:
                    acc = backend.init_acc(arr, weights)
                    ctx.alloc(backend.nbytes(acc))
                else:
                    acc = backend.accumulate(acc, arr, i, weights)
                    ctx.compute(backend.nbytes(arr))
                ctx.free(backend.nbytes(arr))         # buffered slot released
                win.folded()
                continue
            j = win.next_fetch(ctx.now_s)
            arr = ctx.get(store, in_keys[j])          # stalls if unavailable
            ctx.alloc(backend.nbytes(arr))            # buffered input
            buffered[j] = arr
            win.fetched(j)
        out = backend.finalize(acc, weights, n)
        ctx.compute(backend.nbytes(out))
        ctx.put(store, out_key, out, if_none_match=True)  # idempotent
        ctx.free(backend.nbytes(out))
        return out

    return body


def _colocated_body(backend: "ExecutionBackend", shared_mem: dict,
                    store: ObjectStore, in_keys: Sequence[str],
                    weights: Sequence[float], out_key: str, is_global: bool):
    """LIFL shared-memory fast path: read partials from node-local memory
    (no S3, no transfer time); only the global result is PUT."""

    def body(ctx):
        acc = None
        for i, key in enumerate(in_keys):
            ctx.wait_key(key)                         # pipelined: producer gate
            arr = shared_mem[key]                     # no S3, no transfer
            if acc is None:
                acc = backend.init_acc(arr, weights)
                ctx.alloc(backend.nbytes(acc))
            else:
                acc = backend.accumulate(acc, arr, i, weights)
                ctx.compute(backend.nbytes(arr))
        out = backend.finalize(acc, weights, len(in_keys))
        ctx.compute(backend.nbytes(out))
        if is_global:
            ctx.put(store, out_key, out, if_none_match=True)
        else:
            shared_mem[out_key] = out
        ctx.free(backend.nbytes(out))
        return out

    return body


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class ExecutionBackend:
    """Primitive-op protocol an engine implements (see module docstring)."""

    name = "?"

    # -- arithmetic primitives used by the body templates --------------------
    def init_acc(self, arr, weights):
        raise NotImplementedError

    def accumulate(self, acc, arr, i, weights):
        raise NotImplementedError

    def finalize(self, acc, weights, n):
        raise NotImplementedError

    #: fold nodes the last ``end_round`` evaluated with the Pallas kernel
    kernel_folds = 0

    def nbytes(self, x) -> int:
        return int(x.nbytes)

    def decode_value(self, codec, payload):
        """Decoded form of a wire payload reaching the fold frontier.
        Default: eager numpy decode (the streaming/incremental engines
        fold real arrays the moment they reach the frontier)."""
        return decode_eager(payload)

    # -- body construction ---------------------------------------------------
    def avg_body(self, store, in_keys, out_key, weights=None,
                 readahead_k=1):
        return _avg_body(self, store, in_keys, out_key, weights,
                         readahead_k)

    def colocated_body(self, shared_mem, store, in_keys, weights, out_key,
                       is_global):
        return _colocated_body(self, shared_mem, store, in_keys, weights,
                               out_key, is_global)

    # -- client-side sharding ------------------------------------------------
    def shard_values(self, flat: np.ndarray, plan: PartitionPlan) -> list:
        """Per-shard values a client uploads (arrays or zero-copy views)."""
        return shard(flat, plan)

    # -- round lifecycle -----------------------------------------------------
    def end_round(self, store: ObjectStore) -> None:
        """Execute any deferred arithmetic and materialize store contents."""


class StreamingBackend(ExecutionBackend):
    """Reference backend: the seed's inline client-by-client numpy loop."""

    name = "streaming"

    def init_acc(self, arr, weights):
        if weights is not None:
            return arr.astype(np.float64) * weights[0]
        return arr.astype(np.float32).copy()

    def accumulate(self, acc, arr, i, weights):
        if weights is not None:
            acc += arr.astype(np.float64) * weights[i]
        else:
            acc += arr
        return acc

    def finalize(self, acc, weights, n):
        if weights is not None:
            return (acc / float(sum(weights))).astype(np.float32)
        return (acc / float(n)).astype(np.float32)


class _PrefixState:
    """Running prefix-fold accumulator of :class:`IncrementalBackend`.

    ``acc`` is the live running sum (f64 when weighted, matching the
    streaming reference's float64 weighted path; f32 otherwise). Scratch is
    one chunk-sized f64 buffer, shared per backend instance, replacing the
    full-size ``arr.astype(f64) * w`` temporaries of the reference.
    """

    __slots__ = ("acc", "weighted", "size")

    def __init__(self, acc: np.ndarray, weighted: bool):
        self.acc = acc
        self.weighted = weighted
        self.size = int(acc.shape[0])

    @property
    def nbytes(self) -> int:
        return int(self.acc.nbytes)


class IncrementalBackend(ExecutionBackend):
    """Eager chunked prefix folds: streaming semantics, batched locality.

    Each contribution is folded into a preallocated accumulator the moment
    the body reads it, chunk by chunk (``CHUNK_ELEMS``), replaying the exact
    per-element IEEE op order of :class:`StreamingBackend` — left-fold
    accumulate, single divide, f32 cast — so ``avg_flat`` is bit-identical.
    Unlike ``batched`` there is no deferred DAG: partial results exist as
    real arrays throughout the round — what an arrival-driven aggregator
    needs — and ``end_round`` is a no-op.
    """

    name = "incremental"

    def __init__(self) -> None:
        self._buf64 = np.empty(CHUNK_ELEMS, np.float64)

    @staticmethod
    def _as_array(arr) -> np.ndarray:
        return arr if isinstance(arr, np.ndarray) else _materialize(arr)

    def init_acc(self, arr, weights):
        arr = self._as_array(arr)
        if weights is not None:
            acc = np.empty(arr.shape[0], np.float64)
            if weights[0] == 1.0:          # exact: *1.0 is the identity
                np.copyto(acc, arr)
            else:
                np.multiply(arr, weights[0], out=acc, dtype=np.float64)
            return _PrefixState(acc, weighted=True)
        return _PrefixState(arr.astype(np.float32).copy(), weighted=False)

    def accumulate(self, acc: _PrefixState, arr, i, weights):
        arr = self._as_array(arr)
        if not acc.weighted:
            np.add(acc.acc, arr, out=acc.acc)
            return acc
        w = weights[i]
        for s in range(0, acc.size, CHUNK_ELEMS):
            e = min(s + CHUNK_ELEMS, acc.size)
            if w == 1.0:
                np.add(acc.acc[s:e], arr[s:e], out=acc.acc[s:e],
                       dtype=np.float64)
            else:
                buf = self._buf64[:e - s]
                np.multiply(arr[s:e], w, out=buf, dtype=np.float64)
                np.add(acc.acc[s:e], buf, out=acc.acc[s:e])
        return acc

    def finalize(self, acc: _PrefixState, weights, n):
        div = float(sum(weights)) if weights is not None else float(n)
        out = np.empty(acc.size, np.float32)
        if acc.weighted:
            for s in range(0, acc.size, CHUNK_ELEMS):
                e = min(s + CHUNK_ELEMS, acc.size)
                buf = self._buf64[:e - s]
                np.divide(acc.acc[s:e], div, out=buf)
                out[s:e] = buf             # f64 -> f32 cast, same as astype
        else:
            np.divide(acc.acc, np.float32(div), out=out)
        return out


class BatchedBackend(ExecutionBackend):
    """Deferred backend: bodies build a DAG of :class:`LazyAverage` nodes;
    ``end_round`` evaluates it vectorized (numpy chunked fold, or the Pallas
    ``fedavg_multi`` kernel for unweighted nodes on TPU hosts; the count
    lands in ``kernel_folds``). ``chips`` spreads the kernel's folds over
    the first that many local devices."""

    name = "batched"

    def __init__(self, use_pallas: bool | None = None,
                 workers: int | str | None = None, chips: int = 1):
        self._use_pallas = use_pallas
        self._pool = get_pool(workers)
        self._chips = chips
        self._nodes: list[LazyAverage] = []
        self._memo: dict = {}

    # -- arithmetic primitives ----------------------------------------------
    def init_acc(self, arr, weights):
        return _PendingAcc(arr, weighted=weights is not None)

    def accumulate(self, acc, arr, i, weights):
        acc.inputs.append(arr)
        return acc

    def finalize(self, acc, weights, n):
        w = [float(x) for x in weights] if weights is not None else None
        key = (tuple(id(x) for x in acc.inputs),
               tuple(w) if w is not None else None)
        node = self._memo.get(key)
        if node is None:
            # retries / speculative duplicates reuse the same node, exactly
            # as their first-write-wins PUTs reuse the same stored value
            node = LazyAverage(acc.inputs, w)
            self._memo[key] = node
            self._nodes.append(node)
        return node

    # -- client-side sharding ------------------------------------------------
    def shard_values(self, flat: np.ndarray, plan: PartitionPlan) -> list:
        return shard_views(flat, plan)

    # -- wire payloads -------------------------------------------------------
    def decode_value(self, codec, payload):
        # lazy: the decode fuses into the chunked DAG evaluation
        # (EncodedView.read is bitwise decode(payload)[s:e])
        return decode_lazy(payload)

    # -- round lifecycle -----------------------------------------------------
    def _pallas_enabled(self) -> bool:
        if self._use_pallas is not None:
            return self._use_pallas
        from repro.kernels import ops as kops
        return kops.kernel_mode() is not None

    def _kernel_ready(self) -> list:
        """The pending nodes the Pallas fold takes: unweighted, non-empty,
        with every input concrete (no lazy ancestors)."""
        return [nd for nd in self._nodes
                if nd.out is None and nd.weights is None and nd.size > 0
                and not any(isinstance(x, LazyAverage) and x.out is None
                            for x in nd.inputs)]

    def _evaluate_pallas(self, ready: list) -> int:
        """Dispatch the ``ready`` nodes to the Pallas fold — one
        byte-bounded ``fedavg_multi`` per client count, reading inputs in
        place (no whole-round stack). Nodes go in the order they were
        recorded, which in a GradsSharding round is plan order (the phase
        invokes shard j's aggregator j-th), so node j's ``out`` is the
        j-th view of the call's one result buffer. Returns the number of
        nodes it folded."""
        import jax

        from repro.kernels import ops as kops

        devices = jax.local_devices()[:self._chips] if self._chips > 1 \
            else None
        by_n: dict[int, list[LazyAverage]] = {}
        for nd in ready:
            by_n.setdefault(len(nd.inputs), []).append(nd)
        # detlint: allow[ORD001] by_n is insertion-ordered by ready-node
        # creation order; each bucket evaluates independently
        for nds in by_n.values():
            outs = kops.fedavg_multi([nd.inputs for nd in nds],
                                     workers=self._pool.workers,
                                     read=_chunk_of, devices=devices)
            for nd, out in zip(nds, outs):
                nd.out = out
        return len(ready)

    def end_round(self, store: ObjectStore) -> None:
        ready = self._kernel_ready() if self._pallas_enabled() else []
        with span("engine.end_round", kernel_folds=len(ready)):
            self.kernel_folds = self._evaluate_pallas(ready)
            _evaluate_nodes(self._nodes, pool=self._pool)
            for key in store.list():
                v = store.peek(key)
                if not isinstance(v, (np.ndarray, bytes, bytearray)) \
                        and hasattr(v, "materialize"):
                    store.swap(key, v.materialize())
            # release the round's DAG (it pins every client gradient) so a
            # backend instance reused across rounds doesn't accumulate them
            self._nodes = []
            self._memo = {}


class HostMeshBackend(BatchedBackend):
    """Multi-device CPU path: the batched DAG with ``shard_map`` folds.

    Same deferred-DAG recording as :class:`BatchedBackend`; at round end,
    unweighted nodes whose inputs are all concrete dispatch through
    :func:`repro.core.device_agg.mesh_fold_sum` — a ``jax.shard_map``
    left-fold over a 1-D mesh of host CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``), each device
    owning a contiguous element shard — then divide on the host with the
    evaluator's exact f32 op. The on-device fold replays the streaming
    reference's element-wise add chain in order, so the result stays
    bit-identical to every other engine; weighted (f64) folds and nodes
    with lazy ancestors fall back to the numpy chunked evaluator.

    Selection: ``engine="host_mesh"`` (``SessionConfig.host_mesh`` sizes
    the mesh; ``None`` uses every visible CPU device).
    """

    name = "host_mesh"

    def __init__(self, workers: int | str | None = None,
                 n_devices: int | None = None):
        # the Pallas dispatch is superseded by the mesh dispatch here
        super().__init__(use_pallas=False, workers=workers)
        from repro.core import device_agg
        self._mesh = device_agg.make_fold_mesh(n_devices)

    def _evaluate_mesh(self) -> None:
        from repro.core import device_agg

        ready = [nd for nd in self._nodes
                 if nd.out is None and nd.weights is None and nd.size > 0
                 and not any(isinstance(x, LazyAverage) and x.out is None
                             for x in nd.inputs)]
        for nd in ready:
            stack = np.stack([np.asarray(_materialize(x), np.float32)
                              for x in nd.inputs])
            total = device_agg.mesh_fold_sum(self._mesh, stack)
            nd.out = np.empty(nd.size, np.float32)
            # same single f32 divide as _node_chunk — bits preserved
            np.divide(total, np.float32(float(len(nd.inputs))), out=nd.out)

    def end_round(self, store: ObjectStore) -> None:
        self._evaluate_mesh()
        super().end_round(store)


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------

DEFAULT_ENGINE = "batched"

ENGINES = ("streaming", "batched", "incremental", "host_mesh")


def get_backend(engine: str | ExecutionBackend | None = None, *,
                workers: int | str | None = None,
                host_mesh: int | None = None,
                chips: int = 1) -> ExecutionBackend:
    """Resolve the engine knob: an instance, a name, ``None``/"auto" (env
    ``REPRO_AGG_ENGINE``, else ``"batched"``).

    ``workers`` sizes the :class:`~repro.core.fold_pool.ParallelFoldPool`
    behind the batched/host_mesh evaluators (``None`` defers to
    ``REPRO_AGG_WORKERS``, else the host's real core count); the
    streaming and incremental engines are arrival-driven and fold one
    contribution at a time, so the knob is inert there. ``host_mesh``
    sizes the ``host_mesh`` engine's CPU device mesh and is rejected for
    any other engine. ``chips`` spreads the ``batched`` engine's kernel
    folds over that many local devices and is rejected, above 1, for any
    other engine. Backends are stateful per round — this returns a
    fresh instance (pools are shared per worker count).
    """
    if isinstance(engine, ExecutionBackend):
        return engine
    if engine is None or engine == "auto":
        engine = knobs.env_engine(DEFAULT_ENGINE)
    if host_mesh is not None and engine != "host_mesh":
        raise ValueError(
            f"host_mesh={host_mesh} requires engine='host_mesh', "
            f"got engine={engine!r}")
    if chips > 1 and engine != "batched":
        raise ValueError(
            f"chips={chips} needs engine='batched', got engine={engine!r}")
    if engine == "streaming":
        return StreamingBackend()
    if engine == "batched":
        return BatchedBackend(workers=workers, chips=chips)
    if engine == "incremental":
        return IncrementalBackend()
    if engine == "host_mesh":
        return HostMeshBackend(workers=workers, n_devices=host_mesh)
    raise ValueError(f"unknown aggregation engine {engine!r} "
                     f"(expected one of {ENGINES} or 'auto')")

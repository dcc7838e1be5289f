"""Host-resident tensor streaming: train graphs larger than HBM.

The reference's scaling-beyond-framebuffer mechanism is host residency:
every tensor lives in zero-copy host memory and each GPU task stages
its working set through a 4-slot framebuffer cache
(``types.cu:22-32``, ``load_task.cu:365-374``, ``resourcemanager.cc:
29-57``) — a graph only has to fit in host RAM.  The TPU-native analog
keeps the *input features* (the dominant tensor: ``[V, in_dim]``) in
host RAM and streams row blocks through HBM:

- :func:`streamed_linear` — the first-layer projection ``X @ W``
  computed block-by-block.  The projected ``[V, hidden]`` activations
  are HBM-resident from then on, so the rest of the model runs the
  normal fast path.  This covers the common out-of-core case (huge raw
  features, modest hidden width).
- :class:`StreamingAggregator` — full out-of-core neighbor aggregation
  for when even per-layer activations exceed HBM: edges are statically
  grouped by *source block* (host-side, once); per block, the block's
  feature rows are staged to HBM, gathered locally, and scatter-added
  into the output by destination.  Exactly the reference's
  stage-compute-writeback loop, with the FB cache slot replaced by a
  device-resident block buffer.

Every path stages through :class:`StagingPool` — the piece that makes
the tier *latency-hiding* instead of latency-serial: the reference's
ZC→FB loop overlaps the DRAM→GPU copy of the next task's working set
with the current task's kernel, and the pool reproduces that overlap
by running block k+1's host copy + H2D issue on a background thread
while block k's compute is dispatched.  ``prefetch=0`` degrades to the
synchronous form (bit-identical results — the parity reference).
"""

from __future__ import annotations

import functools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph
# import-light (os/signal/dataclasses + the jax-free event bus): the
# fault-drill hook below sits on the per-block staging path, so the
# lookup must not repeat per block
from ..resilience.inject import maybe_staging_error


class _StageError:
    """Worker-side exception carrier (re-raised on the consumer)."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class StagingPool:
    """Reusable double-buffered host→device staging pipeline.

    ``stream(fns)`` yields each stage function's result in order.  With
    ``depth >= 1`` a daemon worker thread runs up to ``depth`` stage
    calls ahead of the consumer, so the blocking host work of block
    k+1 (``np.ascontiguousarray`` copy + ``device_put`` issue) executes
    under block k's compute — the reference's ZC→FB overlap
    (``load_task.cu:365-374``) with the FB slot replaced by a staged
    device buffer.  ``depth == 0`` stages inline (synchronous): the
    bit-identical parity reference and the honest baseline the
    ``overlap_frac`` metric compares against.

    Live-buffer bound: the worker acquires one of ``depth`` credits
    before each stage call and the consumer returns the credit when it
    dequeues, so at most ``depth + 1`` staged blocks exist at any time
    (the one the consumer holds plus the prefetched ones) — with the
    default ``depth=1`` the pool is exactly a 2-slot double buffer,
    regardless of how many blocks V splits into.

    Stats (reset by :meth:`take_stats`): per-block consumer-side
    ``h2d_wait_ms`` (time blocked waiting for a staged block — the
    un-hidden part of the transfer) and worker-side ``stage_ms`` (host
    copy + H2D issue wall time).  ``1 - wait/stage`` is the fraction
    of staging latency hidden under compute (``overlap_frac``).
    """

    def __init__(self, depth: int = 1):
        self.depth = int(depth)
        if self.depth < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {depth}")
        self.h2d_wait_ms: List[float] = []
        self.stage_ms: List[float] = []
        # monotonic start times parallel to the two series — the
        # cross-process timeline (obs/timeline.py) places each block's
        # wait/stage on the merged time axis
        self.h2d_wait_t0: List[float] = []
        self.stage_t0: List[float] = []
        self.max_live = 0
        self._live = 0
        self._lock = threading.Lock()

    def _note_live(self, delta: int) -> None:
        with self._lock:
            self._live += delta
            if self._live > self.max_live:
                self.max_live = self._live

    def take_stats(self) -> Dict[str, object]:
        """Return accumulated per-block stats and reset the series
        (``max_live`` is a lifetime high-water mark and persists).
        The derived summary — ``wait_p50_ms``, ``stage_p50_ms``,
        ``overlap_frac`` (clamped ``1 - wait_total/stage_total``;
        None when nothing was staged) — is computed HERE, once, so
        every consumer (trainer epoch records, micro_stream)
        reports identical semantics."""
        with self._lock:
            wait, stage = self.h2d_wait_ms, self.stage_ms
            wait_t0, stage_t0 = self.h2d_wait_t0, self.stage_t0
            self.h2d_wait_ms, self.stage_ms = [], []
            self.h2d_wait_t0, self.stage_t0 = [], []
            # under the lock: the worker bumps it via _note_live
            # concurrently (roc-lint unguarded-shared-state)
            max_live = self.max_live
        out: Dict[str, object] = {
            "n": len(wait), "wait_ms": wait, "stage_ms": stage,
            "wait_t0": wait_t0, "stage_t0": stage_t0,
            "max_live": max_live, "depth": self.depth,
            "wait_p50_ms": None, "stage_p50_ms": None,
            "overlap_frac": None}
        # these float()s reduce host-side python lists of wall-clock
        # ms — no device array is ever fetched here
        if wait:
            # host stats: roc-lint: ok=host-sync-hot-path
            out["wait_p50_ms"] = round(float(np.median(wait)), 3)
        if stage:
            # host stats: roc-lint: ok=host-sync-hot-path
            out["stage_p50_ms"] = round(float(np.median(stage)), 3)
            total = float(sum(stage))   # host stats: roc-lint: ok=host-sync-hot-path
            if total > 0:
                out["overlap_frac"] = round(min(1.0, max(
                    # host stats: roc-lint: ok=host-sync-hot-path
                    0.0, 1.0 - float(sum(wait)) / total)), 4)
        return out

    def stream(self, stage_fns: Sequence[Callable[[], object]]
               ) -> Iterator[object]:
        """Yield ``fn()`` for each staging function, in order, staging
        up to ``depth`` calls ahead on a worker thread."""
        fns = list(stage_fns)
        # live accounting is per-pass: a consumer that stops pulling
        # (zip with a shorter iterator) leaves the generator suspended
        # mid-yield, so decrements happen at the NEXT dequeue (when the
        # consumer's loop variable has provably been rebound), and the
        # counter resets here
        with self._lock:
            self._live = 0
        if self.depth == 0:
            first = True
            for fn in fns:
                if not first:
                    self._note_live(-1)  # previous block superseded
                first = False
                mono0 = time.monotonic()
                t0 = time.perf_counter()
                val = fn()
                ms = (time.perf_counter() - t0) * 1e3
                with self._lock:
                    self.stage_ms.append(ms)
                    self.stage_t0.append(mono0)
                    # synchronous: the whole stage sits on the critical
                    # path, so the wait IS the stage time
                    self.h2d_wait_ms.append(ms)
                    self.h2d_wait_t0.append(mono0)
                self._note_live(+1)
                yield val
            return

        q: "queue.Queue" = queue.Queue()
        credits = threading.Semaphore(self.depth)
        cancel = threading.Event()

        def work():
            try:
                for fn in fns:
                    while not credits.acquire(timeout=0.1):
                        if cancel.is_set():
                            return
                    if cancel.is_set():
                        return
                    mono0 = time.monotonic()
                    t0 = time.perf_counter()
                    val = fn()
                    with self._lock:
                        self.stage_ms.append(
                            (time.perf_counter() - t0) * 1e3)
                        self.stage_t0.append(mono0)
                    self._note_live(+1)
                    q.put(val)
                    val = None  # the queue owns the only worker ref
            except BaseException as e:  # noqa: BLE001 - re-raised below
                q.put(_StageError(e))

        worker = threading.Thread(target=work, daemon=True,
                                  name="roc-tpu-staging")
        worker.start()
        try:
            for i in range(len(fns)):
                mono0 = time.monotonic()
                t0 = time.perf_counter()
                item = q.get()
                with self._lock:
                    self.h2d_wait_ms.append(
                        (time.perf_counter() - t0) * 1e3)
                    self.h2d_wait_t0.append(mono0)
                if isinstance(item, _StageError):
                    raise item.exc
                if i > 0:
                    # asking for block i means the consumer's loop
                    # rebound its variable: block i-1 is released
                    self._note_live(-1)
                # credit back BEFORE the yield: the worker stages the
                # next block while the consumer computes on this one —
                # that concurrency is the entire point of the pool
                credits.release()
                yield item
        finally:
            cancel.set()


def _stage_block(feats_host: np.ndarray, lo: int, hi: int) -> jax.Array:
    """The ONE sanctioned synchronous host→device staging call site:
    contiguous host copy + async ``device_put`` of one row block.
    Loops never call this directly — they route through
    :meth:`StagingPool.stream` (enforced by roc-lint
    ``sync-h2d-in-loop``).  Also the streamed tier's fault-drill
    site: an armed ``staging_io`` fault raises OSError here once, and
    the recovery loop must restore-and-retry (tests/test_drills.py)."""
    maybe_staging_error()
    return jax.device_put(np.ascontiguousarray(feats_host[lo:hi]))


def streamed_linear(feats_host: np.ndarray, weight: jax.Array,
                    block_rows: int = 65536,
                    dtype=jnp.float32, prefetch: int = 1) -> jax.Array:
    """``feats @ weight`` with ``feats`` in host RAM, streamed through
    HBM in ``block_rows``-row blocks (block k+1 staged under block k's
    matmul).  Returns the device-resident ``[V, out_dim]`` result.
    Peak HBM: two blocks (the double buffer) + the output."""
    V = feats_host.shape[0]
    pool = StagingPool(depth=prefetch)
    stage = [functools.partial(_stage_block, feats_host, lo,
                               lo + block_rows)
             for lo in range(0, V, block_rows)]
    outs = [jnp.asarray(block, dtype=dtype) @ weight
            for block in pool.stream(stage)]
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


# Device-residency budget for cached index tables: plans whose total
# int32 (src, dst) bytes fit keep them device-resident for their
# lifetime (uploaded once at plan build — they used to be re-uploaded
# by ``jnp.asarray`` on every aggregator call); plans past the budget
# fall back to transient per-call uploads, because pinning O(E) index
# bytes on device would defeat the out-of-core tier on exactly the
# >HBM graphs it exists for (one edge_chunk of a transient upload is
# ~8 MB; a billion-edge resident table would be ~8 GB).
TABLE_CACHE_BYTES = 1 << 30


def _iter_chunks(src: np.ndarray, dst: np.ndarray, edge_chunk: int):
    for e0 in range(0, src.shape[0], edge_chunk):
        yield (jnp.asarray(src[e0:e0 + edge_chunk]),
               jnp.asarray(dst[e0:e0 + edge_chunk]))


def _dev_chunks(src: np.ndarray, dst: np.ndarray, edge_chunk: int,
                cache: Optional[dict]):
    """Chunked device-resident (src, dst) index pairs.  ``cache`` is
    the plan's memo dict (upload once, keep for the plan's lifetime)
    or None — the over-:data:`TABLE_CACHE_BYTES` fallback, which
    yields LAZILY so only one edge_chunk of transient index upload is
    live at a time (eagerly materializing the list would re-pin the
    whole O(E) table the budget exists to keep off the device)."""
    if cache is None:
        return _iter_chunks(src, dst, edge_chunk)
    chunks = cache.get(edge_chunk)
    if chunks is None:
        chunks = list(_iter_chunks(src, dst, edge_chunk))
        cache[edge_chunk] = chunks
    return chunks


@dataclass
class _SrcBlockPlan:
    """Static per-source-block edge layout (host-side, built once)."""
    lo: int                 # first global source row of the block
    hi: int                 # one past the last
    src_local: np.ndarray   # int32 [E_b] source ids relative to lo
    dst: np.ndarray         # int32 [E_b] destination rows (sorted)
    _dev: dict = field(default_factory=dict, repr=False, compare=False)

    def dev_chunks(self, edge_chunk: int, cache: bool = True):
        return _dev_chunks(self.src_local, self.dst, edge_chunk,
                           self._dev if cache else None)


class StreamingAggregator:
    """Out-of-core CSR sum-aggregation: ``out[dst] = sum feats[src]``
    with ``feats`` in host RAM.

    Edges are grouped by source block at construction (static for the
    life of the graph, like the reference's partition-time layout) and
    the per-block index tables are uploaded to the device HERE, once —
    while their total bytes fit ``table_cache_bytes``; past that they
    upload transiently per call (O(E) resident index bytes would
    defeat the out-of-core tier at the scales it exists for).  Each
    ``__call__`` streams the feature blocks through the staging
    pool (block k+1's host copy + H2D under block k's scatter-add) and
    accumulates with a sorted segment scatter-add.  Memory on device:
    two feature blocks (the double buffer) + the ``[num_rows, F]``
    output + an edge-chunk transient.  This is the capability tier —
    the in-HBM impls in ``ops/aggregate.py`` are strictly faster when
    features fit.
    """

    def __init__(self, graph: Graph, block_rows: int = 65536,
                 edge_chunk: int = 1 << 20, prefetch: int = 1,
                 table_cache_bytes: int = TABLE_CACHE_BYTES):
        self.num_rows = graph.num_nodes
        self.block_rows = block_rows
        self.edge_chunk = edge_chunk
        self.pool = StagingPool(depth=prefetch)
        dst_all = graph.edge_dst()
        src_all = graph.col_idx
        # group edges by source block; within a block keep dst order
        # (stable sort) so the scatter-add sees sorted segment ids
        block_of = src_all // block_rows
        order = np.argsort(block_of, kind="stable")
        src_s, dst_s = src_all[order], dst_all[order]
        blocks_present = np.unique(block_of)
        self.plans: List[_SrcBlockPlan] = []
        starts = np.searchsorted(block_of[order], blocks_present,
                                 side="left")
        ends = np.searchsorted(block_of[order], blocks_present,
                               side="right")
        for b, lo_e, hi_e in zip(blocks_present, starts, ends):
            lo = int(b) * block_rows
            hi = min(lo + block_rows, self.num_rows)
            sl = src_s[lo_e:hi_e] - lo
            dl = dst_s[lo_e:hi_e]
            o = np.argsort(dl, kind="stable")
            self.plans.append(_SrcBlockPlan(
                lo=lo, hi=hi, src_local=sl[o].astype(np.int32),
                dst=dl[o].astype(np.int32)))
        # device-resident index tables, uploaded once at plan build —
        # but only when their total bytes fit the residency budget:
        # past it, calls fall back to transient per-chunk uploads
        # (this tier exists for graphs that do NOT fit on device)
        idx_bytes = sum(p.src_local.nbytes + p.dst.nbytes
                        for p in self.plans)
        self.cache_tables = idx_bytes <= table_cache_bytes
        if self.cache_tables:
            for plan in self.plans:
                plan.dev_chunks(edge_chunk)

    def __call__(self, feats_host: np.ndarray,
                 out_dtype=jnp.float32) -> jax.Array:
        F = feats_host.shape[1]
        out = jnp.zeros((self.num_rows, F), dtype=out_dtype)
        add = _block_scatter_add_jit
        stage = [functools.partial(_stage_block, feats_host,
                                   plan.lo, plan.hi)
                 for plan in self.plans]
        for plan, block in zip(self.plans, self.pool.stream(stage)):
            # chunk the block's edges to bound the [E, F] transient
            for sl, dl in plan.dev_chunks(self.edge_chunk,
                                          cache=self.cache_tables):
                out = add(out, block, sl, dl)
        return out


def _block_scatter_add(out, block, src_local, dst):
    g = block[src_local].astype(out.dtype)
    return out.at[dst].add(g, indices_are_sorted=True,
                           unique_indices=False)


# module-level jit: the dispatch cache survives across aggregator calls
_block_scatter_add_jit = jax.jit(_block_scatter_add, donate_argnums=(0,))


@dataclass
class _TilePlan:
    """Edges of one (dst block, src block) adjacency tile."""
    src_lo: int
    src_local: np.ndarray   # int32 [E_t] source ids relative to src_lo
    dst_local: np.ndarray   # int32 [E_t] dest ids relative to the dst
    #                         block start (sorted)
    _dev: dict = field(default_factory=dict, repr=False, compare=False)

    def dev_chunks(self, edge_chunk: int, cache: bool = True):
        return _dev_chunks(self.src_local, self.dst_local, edge_chunk,
                           self._dev if cache else None)


def build_tile_plans(graph: Graph, block_rows: int):
    """dst-block -> list of per-src-block edge tiles (host-side, once).
    The fully-out-of-core grouping: BOTH operands of each tile fit in
    one block, so neither the feature matrix nor the output ever has to
    be device-resident whole."""
    dst_all = graph.edge_dst()
    src_all = graph.col_idx
    if not src_all.size:
        return {}
    db = dst_all // block_rows
    sb = src_all // block_rows
    order = np.lexsort((sb, db))
    dst_s, src_s, db_s, sb_s = (dst_all[order], src_all[order],
                                db[order], sb[order])
    # tile boundaries in the lexsorted edge list
    key = db_s.astype(np.int64) * (sb.max() + 1 if sb.size else 1) + sb_s
    cut = np.flatnonzero(np.diff(key)) + 1
    starts = np.concatenate([[0], cut])
    ends = np.concatenate([cut, [key.shape[0]]])
    tiles: dict = {}
    for lo_e, hi_e in zip(starts, ends):
        d, s = int(db_s[lo_e]), int(sb_s[lo_e])
        sl = (src_s[lo_e:hi_e] - s * block_rows).astype(np.int32)
        dl = (dst_s[lo_e:hi_e] - d * block_rows).astype(np.int32)
        o = np.argsort(dl, kind="stable")
        tiles.setdefault(d, []).append(_TilePlan(
            src_lo=s * block_rows, src_local=sl[o], dst_local=dl[o]))
    return tiles


def aggregate_to_host(graph: Graph, feats_host: np.ndarray,
                      block_rows: int = 65536,
                      edge_chunk: int = 1 << 20,
                      tiles=None, prefetch: int = 1,
                      pool: Optional[StagingPool] = None) -> np.ndarray:
    """Fully out-of-core CSR sum-aggregation: both the feature matrix
    AND the result live in host RAM; the device holds one destination
    accumulator block + the double-buffered source feature blocks + an
    edge-chunk transient.  This is the complete form of the reference's
    stage-compute-writeback residency design (``types.cu:22-32``,
    ``load_task.cu:365-374``): *every* [V, F] tensor is host-resident,
    and the next tile's source block stages under the current tile's
    scatter-add.  :class:`StreamingAggregator` (device-resident output)
    is the faster tier when the output fits."""
    V = graph.num_nodes
    F = feats_host.shape[1]
    if tiles is None:
        tiles = build_tile_plans(graph, block_rows)
    if pool is None:
        pool = StagingPool(depth=prefetch)
    out = np.zeros((V, F), dtype=np.float32)
    work = [(d, t) for d in sorted(tiles) for t in tiles[d]]
    # index tables stay device-resident across calls only while they
    # fit the residency budget (stream_prefix_to_host reuses the same
    # tiles across its whole chain); past it they upload transiently —
    # this is the fully-out-of-core tier, where pinning O(E) index
    # bytes on device would defeat the point
    idx_bytes = sum(t.src_local.nbytes + t.dst_local.nbytes
                    for _, t in work)
    cache_tables = idx_bytes <= TABLE_CACHE_BYTES
    stage = [functools.partial(_stage_block, feats_host, t.src_lo,
                               t.src_lo + block_rows)
             for _, t in work]
    acc = None
    cur_d = None
    for (d, t), block in zip(work, pool.stream(stage)):
        if d != cur_d:
            if acc is not None:
                d_lo = cur_d * block_rows
                out[d_lo:d_lo + acc.shape[0]] = np.asarray(acc)
            cur_d = d
            rows = min(block_rows, V - d * block_rows)
            acc = jnp.zeros((rows, F), dtype=jnp.float32)
        for sl, dl in t.dev_chunks(edge_chunk, cache=cache_tables):
            acc = _block_scatter_add_jit(acc, block, sl, dl)
    if acc is not None:
        d_lo = cur_d * block_rows
        out[d_lo:d_lo + acc.shape[0]] = np.asarray(acc)
    return out


def _prefix_op_view(op) -> tuple:
    """``(kind, attrs)`` of a prefix op — accepts both the builder's
    ``_Op`` objects (the trainer's streamable_agg_head path) and the
    plain-dict descriptors the serve manifest persists
    (``roc_tpu/serve/propagation.py``), so BOTH consumers walk the
    identical numeric path below."""
    if isinstance(op, dict):
        return op["kind"], op
    return op.kind, op.attrs


def stream_prefix_to_host(graph: Graph, prefix_ops,
                          feats_host: np.ndarray,
                          block_rows: int = 65536,
                          prefetch: int = 1,
                          capture=None) -> np.ndarray:
    """Evaluate a parameter-free norm/aggregation prefix (the op list
    returned by ``Model.streamable_agg_head``, or its serialized dict
    form) with every [V, F] intermediate host-resident:
    ``indegree_norm`` is a host row scaling, ``scatter_gather``
    (SUM/AVG) runs through :func:`aggregate_to_host` (one staging pool
    reused across the whole chain).  Returns fp32; runs ONCE per
    training session — this is the SGC-style precompute (A_hat^k X),
    after which epochs touch only the streamed head.

    ``capture`` receives each post-op stage table: a plain list (or
    anything with ``.append``) keeps the fp32 arrays — the per-stage
    tables the serve tier's incremental invalidation needs
    (``serve/propagation.PropagationCache``) — while a CALLABLE is
    invoked with each stage instead, which is the quantized-export
    hook (``serve/quant.QuantizingCapture`` encodes each stage as it
    streams, so the >RAM export's host peak holds ONE fp32 stage, not
    all k).  Either way the sink receives an exclusively-owned array
    (see the no-defensive-copy note below).  ONE walk for the
    trainer's precompute and the serving table, so the two can never
    diverge numerically."""
    from ..models.builder import AGGR_AVG, AGGR_SUM
    from ..ops.norm import inv_sqrt_degree_np
    x = np.asarray(feats_host, dtype=np.float32)
    deg = np.asarray(graph.in_degree, dtype=np.float32)
    inv_sqrt = inv_sqrt_degree_np(graph.in_degree)[:, None]
    tiles = None
    pool = StagingPool(depth=prefetch)
    for op in prefix_ops:
        kind, attrs = _prefix_op_view(op)
        if kind == "indegree_norm":
            x = x * inv_sqrt
        elif kind == "scatter_gather":
            if tiles is None:
                tiles = build_tile_plans(graph, block_rows)
            x = aggregate_to_host(graph, x, block_rows, tiles=tiles,
                                  pool=pool)
            if attrs.get("aggr", AGGR_SUM) == AGGR_AVG:
                x = x / np.maximum(deg, 1.0)[:, None]
        elif kind == "fused_aggregate":
            # the fused norm -> sum -> norm [-> relu] op
            # (models/builder.py fuse_norm_aggregate), unrolled
            # host-side — this precompute runs once, so fusion buys
            # nothing here and exactness is what matters
            if tiles is None:
                tiles = build_tile_plans(graph, block_rows)
            x = aggregate_to_host(graph, x * inv_sqrt, block_rows,
                                  tiles=tiles, pool=pool) * inv_sqrt
            if attrs.get("activation", "none") != "none":
                np.maximum(x, 0.0, out=x)
        else:  # pragma: no cover - guarded by streamable_agg_head
            raise NotImplementedError(kind)
        if capture is not None:
            # no defensive copy: every branch above REBINDS x to a
            # fresh array (the fused relu's in-place np.maximum runs
            # before this append), so each captured stage is
            # exclusively owned — a copy would double the host peak
            # of the >HBM export this path exists for
            if callable(capture):
                capture(x)
            else:
                capture.append(x)
    return x


class StreamedHead:
    """First model layer (``dropout -> linear``) computed from
    host-resident features, with the matching streamed weight gradient.

    This is the *integrated* form of :func:`streamed_linear` — the
    piece that makes ``TrainConfig(features="host")`` a training path,
    not just a forward helper.  Forward: per 65536-row block, stage the
    block to HBM through the staging pool (block k+1's host copy + H2D
    issued under block k's compute), apply inverted dropout (key folded
    per block), matmul into the ``[V, H]`` output.  Backward: given the
    cotangent ``dY`` of the projected activations (from autodiff of the
    device-resident tail), ``dW = sum_b dropout(X_b)^T @ dY_b`` with
    the SAME per-block keys, so the recomputed masks match the forward
    exactly; the per-block ``dY`` slice happens INSIDE the jitted block
    fn (a dynamic-slice on the device-resident cotangent — no per-block
    host dispatch or copy).  The raw ``[V, F]`` feature matrix never
    resides on device, and each staged block's last reference drops as
    its block fn consumes it (the running ``dW`` is donated — the one
    buffer here that can alias), so the pool holds at most 2 block
    buffers regardless of V — the reference's ZC->FB staging loop
    (``types.cu:22-32``) with the FB cache slots replaced by the
    double-buffered block transients.

    ``prefetch`` is the pool depth: 0 = synchronous (bit-identical —
    the per-block ``fold_in`` keys do not depend on staging order).

    Note the RNG stream differs from the in-HBM path (one key per
    block instead of one for the whole matrix): both are valid
    inverted-dropout samplings; numerics match exactly in eval mode.
    """

    def __init__(self, rate: float, block_rows: int = 65536,
                 prefetch: int = 1):
        self.rate = float(rate)
        self.block_rows = block_rows
        self.pool = StagingPool(depth=prefetch)

    def _keys(self, key, n_blocks: int):
        if key is None:
            return [None] * n_blocks
        return [jax.random.fold_in(key, b) for b in range(n_blocks)]

    def _blocks(self, V: int):
        return [(lo, min(lo + self.block_rows, V))
                for lo in range(0, V, self.block_rows)]

    def _stage_fns(self, feats_host, blocks):
        return [functools.partial(_stage_block, feats_host, lo, hi)
                for lo, hi in blocks]

    def forward(self, weight: jax.Array, feats_host: np.ndarray,
                key: Optional[jax.Array], train: bool) -> jax.Array:
        """[V, H] projected activations, device-resident."""
        blocks = self._blocks(feats_host.shape[0])
        keys = self._keys(key, len(blocks))
        outs = []
        for k, x in zip(keys, self.pool.stream(
                self._stage_fns(feats_host, blocks))):
            outs.append(_head_fwd_block(x, weight, self.rate, k,
                                        train and key is not None))
        return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]

    def wgrad(self, feats_host: np.ndarray, dY: jax.Array,
              key: Optional[jax.Array], train: bool) -> jax.Array:
        """dL/dW for the head linear, streamed: recomputes each block's
        dropout with the same folded key as :meth:`forward`."""
        blocks = self._blocks(feats_host.shape[0])
        keys = self._keys(key, len(blocks))
        # accumulate across blocks in fp32 regardless of the compute
        # dtype (many-block bf16 accumulation would round away small
        # contributions); the caller casts to the master param dtype
        dW = jnp.zeros((feats_host.shape[1], dY.shape[1]),
                       dtype=jnp.float32)
        for (lo, hi), k, x in zip(blocks, keys, self.pool.stream(
                self._stage_fns(feats_host, blocks))):
            dW = _head_wgrad_block(dW, x, dY, lo, hi - lo, self.rate,
                                   k, train and key is not None)
        return dW


@functools.partial(jax.jit, static_argnames=("rate", "use_mask"))
def _head_fwd_block(x, weight, rate, key, use_mask):
    # dense.linear, not a bare @: the in-HBM path accumulates fp32 at
    # HIGHEST precision and the streamed path must match bit-for-bit
    # semantics (Model.streamable_head guarantees activation == NONE).
    # x (the staged [B, F] block) is deliberately NOT donated: no
    # output shares its shape, so donation could never alias — it
    # would only emit per-compile "donated buffers were not usable"
    # warnings; the buffer frees by refcount once this block fn
    # consumes it, which is what keeps the pool at 2 slots.
    from ..ops.dense import AC_MODE_NONE, dropout, linear
    x = x.astype(weight.dtype)
    d = dropout(x, rate if use_mask else 0.0, key, use_mask)
    return linear(d, weight, AC_MODE_NONE)


@functools.partial(jax.jit,
                   static_argnames=("rows", "rate", "use_mask"),
                   donate_argnums=(0,))
def _head_wgrad_block(dW, x, dY, lo, rows, rate, key, use_mask):
    # dY stays whole and device-resident; the per-block slice is a
    # dynamic-slice INSIDE the jit (one compile for the uniform blocks
    # + one for the tail — no per-block host-side slice dispatch).
    # dW (the running accumulator) is donated — it aliases the output
    # exactly; x cannot alias anything (see _head_fwd_block) and dY is
    # read by every block, so neither is.
    from ..ops.dense import dropout
    x = x.astype(dY.dtype)
    dy = jax.lax.dynamic_slice_in_dim(dY, lo, rows, axis=0)
    d = dropout(x, rate if use_mask else 0.0, key, use_mask)
    prec = (jax.lax.Precision.HIGHEST if d.dtype == jnp.float32
            else None)
    return dW + jax.lax.dot_general(
        d, dy, (((0,), (0,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32).astype(dW.dtype)

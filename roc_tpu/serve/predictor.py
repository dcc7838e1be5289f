"""The serving runtime: bucketed, AOT-warmable predict programs.

A :class:`Predictor` owns frozen params plus ONE compiled program per
batch bucket — the finite, auditable program set the ISSUE's serving
tier is built around:

- ``backend='precomputed'`` (the SGC/APPNP fixed-propagation family):
  a device-resident propagation table (``serve/propagation.py``) and a
  per-bucket ``gather rows → dense head`` program — microsecond-scale
  per dispatch, no graph op anywhere on the request path.  Flavor
  ``akx`` carries ``S^k X`` + the dense head; flavor ``table`` carries
  the frozen full-forward logits (the decoupled APPNP shape, where
  propagation runs after the MLP) and the head degenerates to the
  gather itself.
- ``backend='full'``: the honest always-fresh path — every dispatch
  runs the full-graph forward (the same resolved aggregation layout
  the trainer used) and gathers the queried rows on device.  This is
  the baseline the ``benchmarks/micro_serve.py`` speedup is measured
  against, and the fallback for models whose propagation is not fixed.

Request batch sizes quantize to :data:`SERVE_BUCKETS` so the program
set stays finite — the program-space auditor enumerates exactly these
programs (``analysis/programspace.py`` rig ``sgc_serve``) and
``python -m roc_tpu.prewarm`` / the export step AOT-compile them into
the persistent cache, so a cold server process answers its first query
with ZERO new compiles (program-key parity asserted in
tests/test_serve.py).

Every program compiles through ``ObservedJit`` — serve compiles emit
the same ``compile`` events (program key, lower/compile seconds) the
training slots do, so the warm-start assertion is checkable from the
event stream alone.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, \
    Tuple

import numpy as np

from ..obs.events import emit
from .propagation import PropagationCache


class TableVersion(NamedTuple):
    """One atomically-published serving table: ``version`` is a
    monotonically increasing counter, ``table`` the device array every
    dispatch under this version gathers from (the propagation table
    for the precomputed backend, the feature matrix for full-graph).

    The version carries its OWN quantization spec: ``qmode`` says how
    ``table``'s rows are encoded (``"off"`` → fp32/compute-dtype
    values, ``scale`` is None; ``"int8"``/``"fp8"`` → ``table`` holds
    the quantized codes and ``scale`` the per-row fp32 scales the
    serve program dequantizes with in-register).  Dispatch selects the
    PROGRAM by the captured version's qmode, so a mid-rollout
    fp32→int8 swap (:meth:`Predictor.publish_quant`) is bit-exact per
    captured version — the model checker's ``quant-spec-pinned``
    invariant, live on the wire as the ``res.qmode`` field.

    Publishing a new version NEVER mutates the previous one: the new
    buffer is the old one with exactly the affected rows rewritten
    (``.at[rows].set`` — copy-on-write at the device boundary), so a
    microbatch that captured version ``k`` at batch-take finishes
    bit-exact on ``k``'s values while later batches see ``k+1``
    (tests/test_serve_robustness.py pins this with a concurrent
    stress over a live ``add_edges`` publish)."""
    version: int
    table: Any
    scale: Any = None
    qmode: str = "off"

# Quantized microbatch sizes — the ONLY ids shapes a server ever
# dispatches.  Quantization is what keeps the serve program set finite
# and auditable (same philosophy as core/partition.quantize_plan_
# shapes, but bucket sizes are request shapes, not partition shapes —
# the auditor's drift rule exempts them exactly like the streamed
# head's block variants).
SERVE_BUCKETS: Tuple[int, ...] = (1, 8, 64, 512)


class ShardSlice(NamedTuple):
    """One exported propagation-table SLICE (PR 20): the final-stage
    rows a shard owns, plus the fleet-uniform padded layout every
    shard shares.  ``rows_padded`` is max-over-shards owned rows
    rounded up to the partition NODE_MULTIPLE and ``halo`` the staging
    region for cross-shard gathered rows (= the largest serve bucket,
    so one microbatch's foreign rows always fit) — ONE table shape
    ``(rows_padded + halo + 1, F)`` across the fleet means ONE serve
    program set per (qmode, bucket), AOT-warmed once at export.

    ``rows`` carries fp32 values (qmode off) or None; quantized slices
    carry ``codes`` + per-row ``scales`` instead — per-row symmetric
    quantization is row-local, so sliced codes are bit-identical to
    the full table's.  ``scale_guard`` is the EXPORT-gated envelope
    (full-table max scale × slack), not the slice-local max, so
    refresh guarding matches the drift gate's measurement."""
    lo: int
    hi: int
    num_nodes: int
    rows_padded: int
    halo: int
    rows: Optional[np.ndarray] = None
    codes: Optional[np.ndarray] = None
    scales: Optional[np.ndarray] = None
    scale_guard: Optional[float] = None


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (the padded dispatch size); requests past
    the largest bucket split into largest-bucket chunks upstream."""
    for b in buckets:
        if n <= b:
            return b
    return max(buckets)


class Predictor:
    """Frozen-params query engine; see module docstring.

    Construct via :func:`roc_tpu.serve.export.build_predictor` (live
    objects) or :func:`roc_tpu.serve.export.load_predictor` (an
    exported artifact) — the two run the IDENTICAL build path, which
    is what makes export-time program keys and a cold server's
    programs provably the same set.
    """

    def __init__(self, model, config, params,
                 backend: str, buckets: Sequence[int],
                 cache: Optional[PropagationCache] = None,
                 head_model=None, flavor: Optional[str] = None,
                 dataset=None, gctx=None,
                 num_classes: Optional[int] = None,
                 quant: str = "off",
                 shard: Optional[ShardSlice] = None,
                 verbose: bool = False):
        import jax.numpy as jnp

        from ..train.trainer import compute_dtype_of
        self.model = model
        self.config = config
        self.params = params
        self.backend = backend
        self.flavor = flavor
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if not self.buckets or any(b < 1 for b in self.buckets):
            raise ValueError(f"bad serve buckets {buckets!r}")
        self.compute = compute_dtype_of(config)
        self.cache = cache
        self.head_model = head_model
        self.verbose = verbose
        from .quant import check_mode
        self.quant = check_mode(quant)
        if self.quant != "off" and backend != "precomputed":
            raise ValueError(
                "quantized serving applies to the precomputed table "
                "backend only (the full-graph path has no table to "
                "shrink)")
        self._jits: Dict[Tuple[str, int], Any] = {}
        self.scale = None
        # sharded-serving surface (PR 20): None/unset on full-table
        # predictors; the Server reads these via getattr so the two
        # shapes share one dispatch path
        self.shard = None
        self.gather_fn = None
        self.last_gather_ms: Optional[float] = None
        if backend == "precomputed":
            if shard is not None:
                # table-SLICE serving: this predictor owns global ids
                # [lo, hi); every other id is fetched through
                # ``gather_fn`` at query time and staged into the halo
                # region of a batch-local table copy
                self.shard = (int(shard.lo), int(shard.hi))
                self.num_nodes = int(shard.num_nodes)
                self._rows_padded = int(shard.rows_padded)
                self.halo = int(shard.halo)
                self.pad_id = self._rows_padded + self.halo
                self.table, self.scale = self._device_table_shard(
                    shard)
                self._gctx = self._trivial_gctx()
            elif cache is None:
                raise ValueError("precomputed backend needs a "
                                 "PropagationCache (or a ShardSlice)")
            else:
                self.num_nodes = cache.num_nodes
                self.table, self.scale = self._device_table(self.quant)
                self.pad_id = self.num_nodes
                self._gctx = self._trivial_gctx()
        elif backend == "full":
            if dataset is None or gctx is None:
                raise ValueError("full backend needs dataset + gctx "
                                 "(full-graph serving needs the graph "
                                 "by definition)")
            self.num_nodes = dataset.graph.num_nodes
            self.feats = jnp.asarray(dataset.features,
                                     dtype=self.compute)
            self._gctx = gctx
            self.pad_id = 0   # any valid row; padded outputs discarded
        else:
            raise ValueError(f"unknown serve backend {backend!r}; "
                             f"expected 'precomputed' or 'full'")
        self.num_classes = num_classes
        # the versioned-table publish point: a single attribute swap
        # under the lock (readers take a consistent (version, table)
        # snapshot by reading the one attribute — tuple assignment is
        # atomic, the lock serializes WRITERS against each other)
        self._pub_lock = threading.Lock()
        self._published = (
            TableVersion(0, self.table, self.scale, self.quant)
            if backend == "precomputed"
            else TableVersion(0, self.feats))
        self._build_jits(self.quant)

    # ------------------------------------------------------- programs

    def _device_table(self, mode: str):
        """Upload the host propagation table under ``mode``: fp32 →
        the compute dtype; quantized → the ``(codes, scales)`` pair
        the dequant-in-register program gathers from.  A dummy zero
        row at index V absorbs padded ids (its logits are sliced off
        host-side); its scale is 1.0 so padded dequant stays exact
        zeros.  Also (re)pins the scale-envelope guard the
        invalidation path re-checks refreshed rows against."""
        import jax.numpy as jnp
        if mode == "off":
            t = np.concatenate(
                [self.cache.table,
                 np.zeros((1, self.cache.table.shape[1]), np.float32)])
            return jnp.asarray(t, dtype=self.compute), None
        from .quant import SCALE_GUARD_SLACK, quantize_rows
        q, sc = quantize_rows(self.cache.table, mode)
        # host numpy scale vector — build-time bookkeeping, no device
        self._scale_guard = float(sc.max()) * SCALE_GUARD_SLACK  # roc-lint: ok=host-sync-hot-path
        qpad = np.concatenate(
            [q, np.zeros((1, q.shape[1]), dtype=q.dtype)])
        spad = np.concatenate([sc, np.ones(1, np.float32)])
        return jnp.asarray(qpad), jnp.asarray(spad)

    def _device_table_shard(self, sl: ShardSlice):
        """Upload one table SLICE under the fleet-uniform padded
        layout: owned rows at ``[0, hi-lo)``, zeros through
        ``rows_padded`` (NODE_MULTIPLE rounding), ``halo`` staging
        slots for gathered foreign rows, and the pad row last — one
        shape for every shard, so the bucket programs AOT-warmed at
        export cold-load with zero new compiles on any shard.  Also
        keeps the slice's host mirror: :meth:`read_rows` (the gather
        OWNER side) answers from it without a device round trip."""
        import jax.numpy as jnp
        own = sl.hi - sl.lo
        n = self._rows_padded + self.halo + 1
        if self.quant == "off":
            if sl.rows is None:
                raise ValueError("fp32 shard slice carries no rows")
            self._host_rows = np.asarray(sl.rows, dtype=np.float32)
            t = np.zeros((n, self._host_rows.shape[1]), np.float32)
            t[:own] = self._host_rows
            return jnp.asarray(t, dtype=self.compute), None
        from .quant import SCALE_GUARD_SLACK
        if sl.codes is None or sl.scales is None:
            raise ValueError("quantized shard slice needs codes "
                             "+ scales")
        self._host_codes = np.asarray(sl.codes)
        self._host_scales = np.asarray(sl.scales, dtype=np.float32)
        guard = sl.scale_guard
        if guard is None and own:
            # fallback: the slice-local envelope (exports always
            # persist the full-table one)
            guard = float(self._host_scales.max())  # roc-lint: ok=host-sync-hot-path
        self._scale_guard = float(guard or 1.0) * SCALE_GUARD_SLACK
        q = np.zeros((n, self._host_codes.shape[1]),
                     dtype=self._host_codes.dtype)
        q[:own] = self._host_codes
        s = np.ones(n, np.float32)
        s[:own] = self._host_scales
        return jnp.asarray(q), jnp.asarray(s)

    def _trivial_gctx(self):
        """A graph-free context for the dense head: precompute_split
        guarantees no head op touches the graph, so every graph field
        is a stub (the one-element arrays keep the pytree shape
        stable across processes — part of the program key)."""
        import jax.numpy as jnp

        from ..models.tables import EdgeList
        from ..models.builder import GraphContext
        return GraphContext(
            tables=EdgeList(src=jnp.zeros(1, jnp.int32),
                            dst=jnp.zeros(1, jnp.int32)),
            in_degree=jnp.zeros(1, jnp.int32),
            num_rows=1, gathered_rows=1, aggr_impl="segment",
            symmetric=True)

    def _build_jits(self, mode: str) -> None:
        """One ObservedJit per (quant mode, bucket).  Modes get
        DISTINCT program slots (``_q8``/``_qf8`` suffixes) because
        they are distinct programs with distinct arg avals — the
        auditor ratchets the quantized set under its own rig
        (``sgc_serve_q8``) while the fp32 slots stay byte-identical,
        keeping ``sgc_serve`` at budget delta +0."""
        from ..obs.compile_watch import ObservedJit
        for b in self.buckets:
            self._jits[(mode, b)] = ObservedJit(
                self._serve_step, name=self._slot(b, mode),
                verbose=self.verbose)

    _QSUFFIX = {"off": "", "int8": "_q8", "fp8": "_qf8"}

    def _slot(self, bucket: int, mode: str = "off") -> str:
        tag = (f"precomputed_{self.flavor}"
               if self.backend == "precomputed" else "full")
        return f"serve_{tag}{self._QSUFFIX[mode]}:{bucket}"

    def _serve_step(self, *args):
        import jax.numpy as jnp

        from ..train.trainer import cast_floats
        if self.backend == "precomputed":
            if len(args) == 5:
                # quantized: gather the bucket's code rows + scales
                # and dequantize IN-REGISTER — [bucket, F] widens to
                # the compute dtype, the [V, F] table never does (the
                # dequant-hot-path lint rule holds serve/ to this)
                params, qtab, qscale, ids, gctx = args
                x = (jnp.take(qtab, ids, axis=0).astype(self.compute)
                     * jnp.take(qscale, ids)[:, None]
                     .astype(self.compute))
            else:
                params, table, ids, gctx = args
                x = jnp.take(table, ids, axis=0)
            if self.flavor == "table":
                return x
            return self.head_model.apply(
                cast_floats(params, self.compute), x, gctx,
                key=None, train=False)
        params, feats, ids, gctx = args
        logits = self.model.apply(cast_floats(params, self.compute),
                                  feats, gctx, key=None, train=False)
        return jnp.take(logits, ids, axis=0)

    def _args_for(self, ids, pub: Optional[TableVersion] = None):
        """The per-dispatch argument tuple — ONE construction shared
        by the live call path and the candidate enumeration, so the
        auditor/prewarm keys and the runtime programs cannot drift.
        ``pub`` pins a captured table version (the microbatch server
        captures one per batch); None reads the current publication.
        Versions only swap the table VALUES, never its shape/dtype —
        within one qmode the program key is version-independent, and
        across qmodes the captured version routes to ITS mode's
        program (the quant-spec-pinned invariant)."""
        if pub is None:
            pub = self._published
        if pub.qmode != "off":
            return (self.params, pub.table, pub.scale, ids,
                    self._gctx)
        return (self.params, pub.table, ids, self._gctx)

    def serve_candidates(self) -> List[Any]:
        """The exact serve program set, as prewarmable auditor
        candidates (``analysis/programspace.Candidate``) — one program
        per bucket.  ``observed=False``: bucket sizes are request
        shapes, not partition shapes (the cache-key-drift rule's
        head-block exemption applies verbatim), but the programs still
        count against the ``program_budget`` ratchet and the prewarm
        driver AOT-compiles every one."""
        import jax
        import jax.numpy as jnp

        from ..analysis.programspace import Candidate
        cands: List[Any] = []
        quant = self.quant != "off"
        # the quantized 5-tuple splits the table role into codes +
        # scales (both version-swapped data planes); ids/gctx keep
        # their off-mode roles so the replication auditor sees the
        # same sharing story
        roles = (("params", "data", "data", "other", "tables")
                 if quant else ("params", "data", "other", "tables"))
        for b in self.buckets:
            ids = jax.ShapeDtypeStruct((b,), jnp.dtype(jnp.int32))
            args = self._args_for(ids)
            jit = self._jits[(self.quant, b)]._jit
            cands.append(Candidate(
                slot=self._slot(b, self.quant), fn=jit, args=args,
                donate=(), observed=False, roles=roles,
                aot=lambda j=jit, a=args: j.lower(*a).compile()))
        return cands

    def warm(self, cache_dir: Optional[str] = None,
             name: str = "serve") -> Dict[str, Any]:
        """AOT-compile every bucket program against the persistent
        cache (the export step calls this, and a cold server may too —
        first-query readiness becomes a warm-hit report instead of a
        latency spike)."""
        from ..utils.compile_cache import enable_compile_cache
        from ..utils.prewarm import warm_candidates
        d = enable_compile_cache(cache_dir, min_compile_secs=0.0)
        return warm_candidates(self.serve_candidates(), d, config=name,
                               verbose=self.verbose)

    def program_keys(self) -> List[str]:
        from ..obs.compile_watch import program_key_of
        return sorted(program_key_of(c.slot, c.args, c.donate)
                      for c in self.serve_candidates())

    # --------------------------------------------------------- queries

    def table_bytes(self) -> int:
        """Device bytes of the CURRENT published serving table
        (codes + per-row scales when quantized) — what a replica
        advertises on ``ready`` and the per-replica byte budget is
        enforced against.  Sharded predictors report the slice's
        padded O(V/N)+halo footprint, full ones O(V)."""
        from .quant import table_bytes as _tb
        pub = self._published
        return int(_tb(tuple(int(d) for d in pub.table.shape),
                       pub.qmode))

    def published(self) -> TableVersion:
        """A consistent snapshot of the current table version (one
        atomic attribute read).  Dispatch paths capture this ONCE per
        microbatch so every request in the batch is served from one
        version even while :meth:`invalidate` publishes a new one."""
        return self._published

    def query_device(self, ids_padded,
                     pub: Optional[TableVersion] = None):
        """One padded-bucket dispatch; returns the device logits
        ``[bucket, C]``.  ``ids_padded`` length must be a bucket."""
        b = int(ids_padded.shape[0])
        if pub is None:
            pub = self._published
        # the program is selected by the CAPTURED version's qmode —
        # a batch pinned to a fp32 version keeps running the fp32
        # program even after publish_quant lands int8 (quant-spec-
        # pinned, bit-exact per captured version)
        jit = self._jits.get((pub.qmode, b))
        if jit is None:
            raise ValueError(f"ids length {b} is not a bucket "
                             f"{self.buckets}")
        return jit(*self._args_for(ids_padded, pub))

    def query(self, node_ids,
              pub: Optional[TableVersion] = None) -> np.ndarray:
        """Synchronous convenience path: pad to the smallest fitting
        bucket, dispatch, fetch, slice.  The microbatch server
        (``serve/server.py``) is the production entry — it coalesces
        concurrent requests into one dispatch; this method is the
        single-caller form the parity tests pin.

        Sharded predictors accept the SAME global id space: ids this
        shard owns remap to local table rows; foreign ids are fetched
        through ``gather_fn`` (coalesced per chunk — one gather per
        microbatch, version-pinned to ``pub``) and staged into the
        halo slots of a batch-local table copy.  ``last_gather_ms``
        records the chunk-summed gather wall (None when every id was
        owned)."""
        import jax
        import jax.numpy as jnp
        ids = np.asarray(node_ids, dtype=np.int32).ravel()
        if ids.size and (ids.min() < 0 or ids.max() >= self.num_nodes):
            raise ValueError(
                f"node ids out of range [0, {self.num_nodes})")
        if pub is None:
            pub = self.published()  # one version for every chunk
        self.last_gather_ms = None
        out: List[np.ndarray] = []
        cap = max(self.buckets)
        for lo in range(0, ids.size, cap):
            chunk = ids[lo:lo + cap]
            if self.shard is not None:
                chunk, pub_c = self._remap_chunk(chunk, pub)
            else:
                pub_c = pub
            b = bucket_for(chunk.size, self.buckets)
            padded = np.full(b, self.pad_id, dtype=np.int32)
            padded[:chunk.size] = chunk
            logits = self.query_device(jnp.asarray(padded), pub_c)
            # the result fetch IS this tier's product — the one
            # sanctioned host sync on the serve path
            got = jax.device_get(logits)  # roc-lint: ok=host-sync-hot-path
            out.append(np.asarray(got[:chunk.size], dtype=np.float32))
        return (np.concatenate(out) if out
                else np.zeros((0, self.num_classes or 0), np.float32))

    # ------------------------------------------- sharded tables (PR 20)

    def _remap_chunk(self, chunk: np.ndarray,
                     pub: TableVersion
                     ) -> Tuple[np.ndarray, TableVersion]:
        """Global chunk ids → local table rows.  Owned ids offset into
        ``[0, hi-lo)``; foreign ids are gathered (unique, one call)
        and remapped onto their staged halo slots."""
        lo, hi = self.shard
        local = chunk.astype(np.int64) - lo
        foreign = (chunk < lo) | (chunk >= hi)
        if foreign.any():
            uniq = np.unique(chunk[foreign])
            pub, slot_of = self._stage_foreign(uniq, pub)
            local[foreign] = [slot_of[int(g)] for g in chunk[foreign]]
        return local.astype(np.int32), pub

    def _stage_foreign(self, uniq: np.ndarray, pub: TableVersion
                       ) -> Tuple[TableVersion, Dict[int, int]]:
        """Fetch ``uniq`` foreign rows at exactly ``pub.version`` and
        stage them into the halo slots of a batch-local copy-on-write
        table (the published version is never mutated).  The gather is
        PINNED: an answer from any other version or qmode is retried
        once (the owner may be mid-publish) and then refused — the
        model checker's ``gather-version-pinned`` invariant, with the
        ``shard-gather`` seed showing what an unpinned gather mixes."""
        import time as _time

        import jax.numpy as jnp

        from .errors import GatherError
        if self.gather_fn is None:
            raise GatherError(
                f"shard [{self.shard[0]}, {self.shard[1]}) was asked "
                f"for {uniq.size} foreign row(s) but has no gather_fn "
                f"— sharded serving needs the cross-shard gather leg")
        if uniq.size > self.halo:
            raise GatherError(
                f"{uniq.size} unique foreign rows exceed the halo "
                f"staging region ({self.halo}); chunking must cap a "
                f"microbatch at the largest bucket")
        t0 = _time.perf_counter()
        vals, scales, ver, qmode = self.gather_fn(uniq, pub.version)
        if ver != pub.version or qmode != pub.qmode:
            vals, scales, ver, qmode = self.gather_fn(uniq,
                                                      pub.version)
        if ver != pub.version or qmode != pub.qmode:
            raise GatherError(
                f"gather pinned to v{pub.version}:{pub.qmode} was "
                f"answered from v{ver}:{qmode} twice — refusing to "
                f"mix table versions in one microbatch")
        slots = self._rows_padded + np.arange(uniq.size)
        idx = jnp.asarray(slots.astype(np.int32))
        if pub.qmode != "off":
            # quantized gathers ship the owner's stored CODES + per-row
            # scales verbatim — staging them is bit-exact by
            # construction (per-row symmetric quantization is row-local)
            table = pub.table.at[idx].set(
                jnp.asarray(np.asarray(vals),
                            dtype=pub.table.dtype))
            scale = pub.scale.at[idx].set(
                jnp.asarray(np.asarray(scales, dtype=np.float32)))
            staged = TableVersion(pub.version, table, scale,
                                  pub.qmode)
        else:
            table = pub.table.at[idx].set(
                jnp.asarray(np.asarray(vals, dtype=np.float32),
                            dtype=self.compute))
            staged = TableVersion(pub.version, table, None, "off")
        ms = (_time.perf_counter() - t0) * 1e3
        self.last_gather_ms = (self.last_gather_ms or 0.0) + ms
        slot_of = {int(g): int(s) for g, s in zip(uniq, slots)}
        return staged, slot_of

    # ---------------------------------------------------- invalidation

    def invalidate(self, src, dst) -> int:
        """Edge-append invalidation hook: incrementally recompute the
        k-hop neighborhood rows of the propagation table
        (``PropagationCache.add_edges``) and publish a NEW table
        version carrying exactly those rows (``refresh_rows``).
        Returns the number of rows refreshed.  Control-plane op — the
        scatter below compiles a tiny program per affected-set shape,
        deliberately OUTSIDE the audited serve set (mutations are
        rare; quantizing them would complicate the hot path for
        nothing).  Mutators serialize on the publish lock; query
        threads never block on it (they read the published snapshot)."""
        if self.backend != "precomputed" or self.cache is None:
            raise NotImplementedError(
                "invalidation needs the precomputed backend (full-"
                "graph serving recomputes every dispatch anyway)")
        with self._pub_lock:
            rows = self.cache.add_edges(src, dst)
            version = self._publish_rows_locked(rows)
        self._emit_publish(version, rows)
        return int(rows.size)

    def refresh_rows(self, rows: np.ndarray) -> None:
        """Publish a new table version with ``rows`` re-uploaded from
        the host cache.  The previous version's device buffer is left
        untouched — in-flight dispatches pinned to it finish
        bit-exact (``.at[rows].set`` materializes a fresh buffer:
        copy-on-write at the device boundary)."""
        if self.shard is not None:
            raise NotImplementedError(
                "sharded predictors have no full host cache — "
                "refreshes arrive as (rows, values) via apply_refresh")
        with self._pub_lock:
            version = self._publish_rows_locked(rows)
        self._emit_publish(version, rows)

    def read_rows(self, ids, version: int):
        """The gather OWNER side: raw stored rows for ``ids`` (which
        this predictor must own) at exactly table ``version``.
        Returns ``(values, scales, version, qmode)`` — fp32 rows with
        ``scales=None`` for qmode off, else stored codes + per-row
        scales, both host-side (sharded predictors answer from the
        slice's host mirror; full-table ones re-encode from the host
        cache, bit-identical per-row).  A version other than the live
        publication is refused — the requester's pin decides what to
        do (retry, then fail typed), never this side."""
        from .errors import GatherError
        if self.backend != "precomputed":
            raise GatherError("row fetches need the precomputed "
                              "table backend")
        pub = self._published
        if int(version) != pub.version:
            raise GatherError(
                f"row fetch pinned to v{version} refused: this "
                f"replica publishes v{pub.version}")
        ids = np.asarray(ids, dtype=np.int64).ravel()
        lo, hi = self.shard if self.shard is not None \
            else (0, self.num_nodes)
        if ids.size and (ids.min() < lo or ids.max() >= hi):
            raise GatherError(
                f"row fetch for ids outside owned range [{lo}, {hi})")
        local = ids - lo
        with self._pub_lock:
            if self.shard is not None:
                if pub.qmode != "off":
                    return (self._host_codes[local].copy(),
                            self._host_scales[local].copy(),
                            pub.version, pub.qmode)
                return (self._host_rows[local].copy(), None,
                        pub.version, pub.qmode)
            # the REQUESTED rows only (a gather is ≤ halo rows), from
            # the host cache — never a full fp32 table materialization
            vals = np.asarray(self.cache.table[local],  # roc-lint: ok=dequant-hot-path
                              dtype=np.float32)
        if pub.qmode != "off":
            from .quant import quantize_rows
            q, sc = quantize_rows(vals, pub.qmode)
            return q, sc, pub.version, pub.qmode
        return vals, None, pub.version, pub.qmode

    def apply_refresh(self, rows: np.ndarray,
                      values: np.ndarray) -> int:
        """Sharded half of the ``add_edges`` invalidation fan-out: the
        update originator (which holds the FULL PropagationCache) runs
        the k-hop recompute centrally and ships every shard the
        affected (global rows, fp32 values); each shard applies only
        the rows it OWNS and bumps its version either way — data
        lands on owning shards only, while version counters stay
        comparable across the fleet (an epoch-only bump on
        non-owners), which is what keeps cross-shard gathers pinnable
        mid-rollout.  Returns the number of rows this shard applied."""
        import jax.numpy as jnp
        if self.shard is None:
            raise NotImplementedError(
                "apply_refresh is the sharded refresh path; full-"
                "table predictors use invalidate()/refresh_rows()")
        rows = np.asarray(rows, dtype=np.int64).ravel()
        values = np.asarray(values, dtype=np.float32)
        lo, hi = self.shard
        mask = (rows >= lo) & (rows < hi)
        own = rows[mask] - lo
        vals = values[mask]
        with self._pub_lock:
            old = self._published
            version = old.version + 1
            if own.size == 0:
                # epoch-only bump: no owned data changed, but the
                # fleet-wide version counter must advance in lockstep
                self._published = TableVersion(
                    version, old.table, old.scale, old.qmode)
            elif old.qmode != "off":
                from .quant import QuantDriftError, quantize_rows
                q, sc = quantize_rows(vals, old.qmode)
                guard = getattr(self, "_scale_guard", None)
                smax = float(sc.max())  # roc-lint: ok=host-sync-hot-path
                if guard is not None and smax > guard:
                    raise QuantDriftError(
                        f"sharded refresh refused: row scale "
                        f"{smax:.6g} exceeds the gated envelope "
                        f"{guard:.6g}; serving stays on "
                        f"v{old.version}")
                self._host_codes[own] = q
                self._host_scales[own] = sc
                idx = jnp.asarray(own.astype(np.int32))
                table = old.table.at[idx].set(jnp.asarray(q))
                scale = old.scale.at[idx].set(jnp.asarray(sc))
                self.table, self.scale = table, scale
                self._published = TableVersion(version, table, scale,
                                               old.qmode)
            else:
                self._host_rows[own] = vals
                idx = jnp.asarray(own.astype(np.int32))
                table = old.table.at[idx].set(
                    jnp.asarray(vals, dtype=self.compute))
                self.table = table
                self._published = TableVersion(version, table, None,
                                               "off")
        self._emit_publish(version, own)
        return int(own.size)

    def _publish_rows_locked(self, rows: np.ndarray) -> Optional[int]:
        import jax.numpy as jnp
        if rows.size == 0:
            return None
        old = self._published
        idx = jnp.asarray(rows.astype(np.int32))
        if old.qmode != "off":
            # requantize ONLY the recomputed rows.  Per-row symmetric
            # scales are row-local, so these (q, scale) pairs are
            # bit-identical to quantizing a full rebuild of the
            # mutated table (tests/test_serve_quant.py pins it) —
            # incremental invalidation loses nothing to quantization.
            from .quant import QuantDriftError, quantize_rows
            q, sc = quantize_rows(self.cache.table[rows], old.qmode)
            guard = getattr(self, "_scale_guard", None)
            # host numpy scales (control-plane refresh, not a query)
            smax = float(sc.max())  # roc-lint: ok=host-sync-hot-path
            if guard is not None and smax > guard:
                # the post-invalidation drift re-check: a refreshed
                # row whose quantization step left the envelope the
                # export-time gate measured would serve coarser
                # values than anything validated — refuse BEFORE
                # publishing; the old version stays live and the
                # operator re-exports (re-gating) instead
                raise QuantDriftError(
                    f"invalidation refused: refreshed row scale "
                    f"{smax:.6g} exceeds the gated envelope "
                    f"{guard:.6g} (build max × slack); serving "
                    f"stays on v{old.version} — re-export to re-run "
                    f"the drift gate on the mutated graph")
            new_table = old.table.at[idx].set(jnp.asarray(q))
            new_scale = old.scale.at[idx].set(jnp.asarray(sc))
            self.table, self.scale = new_table, new_scale
            self._published = TableVersion(
                old.version + 1, new_table, new_scale, old.qmode)
            return old.version + 1
        vals = jnp.asarray(
            self.cache.table[rows].astype(np.float32),  # roc-lint: ok=dequant-hot-path
            dtype=self.compute)
        new_table = old.table.at[idx].set(vals)
        self.table = new_table
        self._published = TableVersion(
            old.version + 1, new_table, None, "off")
        return old.version + 1

    def publish_quant(self, mode: str) -> int:
        """Control-plane re-publication of the CURRENT host table
        under a new quant spec — the mid-rollout fp32→int8 (or back)
        swap.  The target mode's bucket programs are built before the
        publish so the hot path never constructs programs; the swap
        itself is one versioned publish, and in-flight batches pinned
        to the previous version finish on ITS mode's program against
        ITS buffers (quant-spec-pinned — the model checker's
        ``live-qmode`` seed shows what skipping the pin would serve).
        Returns the published version."""
        from .quant import check_mode
        if self.backend != "precomputed" or self.cache is None:
            raise NotImplementedError(
                "quant swaps apply to the precomputed table backend")
        mode = check_mode(mode)
        if (mode, self.buckets[0]) not in self._jits:
            self._build_jits(mode)
        with self._pub_lock:
            old = self._published
            table, scale = self._device_table(mode)
            self.table, self.scale = table, scale
            self.quant = mode
            version = old.version + 1
            self._published = TableVersion(version, table, scale,
                                           mode)
        emit("serve", f"table version {version} published "
             f"(quant swap {old.qmode}->{mode}; in-flight queries "
             f"finish on v{old.version}:{old.qmode})", console=False,
             kind="table_publish", version=version, rows=0,
             qmode=mode)
        return version

    def _emit_publish(self, version: Optional[int],
                      rows: np.ndarray) -> None:
        # after the publish lock is released: event I/O must never sit
        # on the mutation critical section (roc-lint
        # blocking-under-lock)
        if version is None:
            return
        emit("serve", f"table version {version} published "
             f"({rows.size} row(s) rewritten; in-flight queries "
             f"finish on v{version - 1})", console=False,
             kind="table_publish", version=version,
             rows=int(rows.size))

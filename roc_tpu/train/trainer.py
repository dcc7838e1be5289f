"""Single-device training loop (the reference's ``top_level_task`` epoch
loop, ``gnn.cc:99-111``): per epoch — staircase lr decay, zero grads
(implicit: JAX recomputes), forward, backward, Adam update; every 5th
epoch an inference pass printing train loss + train/val/test accuracy in
the reference's format (``softmax_kernel.cu:141-152``).

The distributed loop lives in ``parallel/distributed.py``; this module is
the minimum end-to-end slice (BASELINE.md config 1/2 path).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, replace as dc_replace
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import Dataset
from ..core.relations import (AGGREGATE_FIRST, ORDER_PASSES, agg_passes,
                              resolve_agg_order)
from ..models.tables import EdgeList, View, build_tables, upload
from ..models.builder import GraphContext, Model
from ..obs.events import emit, flush_spans, span
from ..obs.metrics_registry import MetricsRegistry
from ..obs.scopes import LOSS_SCOPE, OPT_SCOPE
from ..ops.loss import perf_metrics, summarize_metrics
from .optimizer import AdamConfig, adam_init, adam_update, decayed_lr


@dataclass
class TrainConfig:
    """Mirrors the reference ``Config`` struct + CLI defaults
    (``gnn.h:105-113``, ``gnn.cc:30-41``)."""
    learning_rate: float = 0.01
    weight_decay: float = 0.05
    dropout_rate: float = 0.5
    decay_rate: float = 1.0
    decay_steps: int = 100
    epochs: int = 200
    seed: int = 1
    eval_every: int = 5
    verbose: bool = True
    # segment|ell|sectioned|bdense|flat_sum|auto ("auto" picks
    # sectioned in its measured node-count window, ell or flat_sum
    # outside — core/ell.py resolve_auto_impl; bdense by the structure
    # probe, resolve_auto_impl_probed)
    aggr_impl: str = "segment"
    # padding multiple of the edge list the 'segment' reference reads
    chunk: int = 512
    # Aggregation fusion (auto|on|off): rewrite every norm ->
    # sum-aggregate -> norm [-> relu] chain into ONE fused op
    # (models/builder.py fuse_norm_aggregate) with the symmetric
    # D^-1/2 scales baked into the host-built tables where the layout
    # allows (ell/sectioned/bdense/ring — core/ell.py weight tables)
    # and fused pre/post scaling elsewhere.  Exact linear algebra:
    # forward and gradients match the unfused chain to fp32 tolerance
    # (tests/test_fused_agg.py).  "auto" fuses whenever the model has
    # a matching chain; "on" additionally echoes when nothing fused;
    # "off" keeps the reference's separate-op semantics.
    aggr_fuse: str = "auto"
    dtype: Any = jnp.float32
    # Mixed precision: when set (e.g. jnp.bfloat16), params + Adam
    # state stay in ``dtype`` (fp32 master weights) while features,
    # activations, and the aggregation run in ``compute_dtype`` —
    # halving HBM traffic on the bandwidth-bound aggregation and using
    # the MXU's native bf16 multiply path.  Params are cast inside the
    # step; gradients flow back through the cast as fp32 (bf16 shares
    # fp32's exponent range, so no loss scaling is needed; the loss
    # itself is always reduced in fp32, ops/loss.py).  None = compute
    # in ``dtype`` (the reference's pure-fp32 semantics,
    # linear_kernel.cu:76-80).
    compute_dtype: Optional[Any] = None
    # Halo exchange for the distributed step: "gather" (one-shot
    # all_gather, the reference's whole-region semantics) or "ring"
    # (ppermute rotation, O(V/P) peak memory; parallel/ring.py)
    halo: str = "gather"
    # Ring hop schedule (halo='ring'): True (default) issues each
    # hop's ppermute BEFORE the scatter-accumulate of the current
    # buffer — double-buffered, so XLA can overlap the collective
    # with compute.  False keeps the strictly sequential
    # compute-then-permute order (identical numerics; the
    # measurement/debug reference).
    ring_overlap: bool = True
    # Streamed-tier prefetch (features='host'): staging-pool depth —
    # how many feature blocks the background stager runs ahead of
    # compute (core/streaming.py StagingPool).  "auto" resolves to 1
    # (double-buffered: block k+1's host copy + H2D transfer run
    # under block k's compute, peak 2 live block buffers); 0 stages
    # synchronously (bit-identical results — the parity reference
    # the overlap_frac epoch metric compares against).
    prefetch: Any = "auto"
    # Symmetric-adjacency assumption for the aggregation backward (the
    # reference requires it, scattergather_kernel.cu:160-170).
    # None = verify host-side at setup (O(E log E)); True = trust the
    # caller (skip the check, e.g. huge graphs); False = force exact
    # autodiff gradients (directed graphs).
    symmetric: Optional[bool] = None
    # Observability (utils/profiling.py): profiler trace directory
    # (TensorBoard format; None = off) and metrics JSONL path.
    profile_dir: Optional[str] = None
    metrics_path: Optional[str] = None
    # Memory policy (the TPU analog of the reference's FB-cache +
    # zero-copy residency design, resourcemanager.h:30, types.cu:22-32):
    # - remat: compute each run of ops between two aggregations again
    #   in the backward instead of keeping its insides (one
    #   checkpoint a run, models/builder.py Model.apply): the step
    #   keeps the aggregations' outputs and little else, and pays the
    #   dense and elementwise forward twice.  The aggregations are
    #   never computed again: a checkpoint that took one in would keep
    #   its input in place of its output, the same bytes, and pay the
    #   gather and the sum twice — so there is no policy to choose.
    # - features: "hbm" keeps the input features device-resident;
    #   "host" keeps them in host RAM and streams the first layer
    #   (dropout -> linear) through HBM in row blocks, forward AND
    #   weight-gradient (core/streaming.py StreamedHead).  Requires a
    #   streamable model head (Model.streamable_head).
    # - memory: "manual" uses halo/features/remat as given; "auto" runs
    #   core/memory.choose_memory_plan over the dataset/model shapes
    #   and overrides them with the first plan that fits hbm_bytes
    #   (None = detect), echoing the decision at setup.
    remat: bool = False
    features: str = "hbm"
    memory: str = "manual"
    hbm_bytes: Optional[int] = None
    # Sectioned-layout tuning (core/ell.py SectionedEll):
    # - sect_sub_w: neighbors per sub-row (each (row, section) pair
    #   pads to a multiple of it).
    # - sect_u16: uint16 section-local index tables (halves index
    #   bytes; caps section_rows at 65,535 so the dummy id fits).
    sect_sub_w: int = 8
    sect_u16: bool = False
    # - bdense_min_fill: edges per [128,128] tile below which the tile
    #   stays in the sectioned residual (aggr_impl='bdense')
    # - bdense_a_budget: uint8 A-table byte cap (densest blocks kept);
    #   the 2 GiB default was measured BINDING on the community
    #   substrate at Reddit scale — min_fill=32 with a 6 GiB budget
    #   lifts dense_frac 0.52 -> 0.81 (blockdense_occupancy.json
    #   planted16384_lpa_f32_b6g).  None disables the cap.
    bdense_min_fill: int = 64
    bdense_a_budget: Optional[int] = 2 << 30
    # - bdense_group: dense blocks reduced per output-tile update
    #   (pad_plan_groups).  >1 cuts the dominant [128, F] fp32 output
    #   read-modify-write traffic group-x for <= (group-1) zero-A
    #   padding blocks per occupied dst tile.
    bdense_group: int = 1
    # Graph partitioning (distributed only; core/costmodel.py):
    # - partition: "greedy" = the reference's edge-count sweep
    #   (gnn.cc:806-829 semantics), "cost" = the cost-balanced minimax
    #   split over the model's padded-shape surrogate, "auto" = cost
    #   (the cold-start weights ARE quantized edge balance, solved
    #   optimally — never worse than greedy under the model).
    # - rebalance: refit the per-partition cost model against measured
    #   step times at every eval boundary and repartition when the
    #   predicted max-shard gain exceeds rebalance_gain (hysteresis),
    #   at most rebalance_max times per run.  Full-batch training
    #   makes a repartition numerics-preserving; unchanged quantized
    #   shapes reuse the compiled step (no recompile).
    partition: str = "auto"
    rebalance: bool = False
    rebalance_gain: float = 0.10
    rebalance_max: int = 2
    # Chunked output head (the compile-wall fix for the classification
    # head): "auto" chunks the loss-op linear on the vertex axis in
    # HEAD_CHUNK_ROWS blocks once the local row count reaches
    # HEAD_CHUNK_AUTO_MIN_ROWS (below that the full-width matmul is
    # already small), an int >= 0 is a literal block size (0 = off).
    # Values and dX bit-identical either way; dW matches to fp32
    # roundoff (blockwise row-sum order, ops/dense.py linear_chunked);
    # the chunked head's compiled matmul is [block, C] instead of
    # [V_p, C], shape-stable across graph sizes.
    head_chunk: Any = "auto"
    # Persistent compile cache write threshold (utils/compile_cache.py
    # enable_compile_cache min_compile_secs): None defers to the
    # harness default (ROC_TPU_CACHE_MIN_SECS env, else 1.0 s).  The
    # 1.0 s default silently skips caching the many small per-block
    # streamed-head programs; the prewarm driver (utils/prewarm.py)
    # passes 0.0 so EVERY program lands in the cache.  Recorded in
    # the run manifest; consumed by the harnesses (the CLI) that
    # enable the cache — trainers never touch the cache themselves.
    cache_min_compile_secs: Optional[float] = None
    # Async checkpointing (resilience/async_save.py): 'auto' (default)
    # saves asynchronously when this job is single-process — the step
    # path pays only the finite guard + host snapshot while CRC +
    # shard write + manifest commit overlap the next epochs on the
    # saver thread; 'on'/'off' force it.  Multi-process 'auto'
    # resolves OFF: async coalescing decisions are timing-dependent
    # and cannot be assumed identical across SPMD processes (the
    # sharded save's commit barrier needs lockstep), so shared
    # rotations save synchronously unless forced.
    async_save: Any = "auto"
    # Fault injection (resilience/inject.py): arm ONE drill fault for
    # this process as "site:epoch[:proc]" (sites: nan_grads, sigkill,
    # sigterm, kill_in_save, bitflip_checkpoint, staging_io,
    # stall_compile).  None = no fault; the ROC_TPU_FAULT env var is
    # the equivalent out-of-band switch.  Each fault fires at most
    # once per process — the drill harness (tests/test_drills.py)
    # injects, restarts, and asserts the run still finishes.
    fault: Optional[str] = None
    # Device mesh shape "PxM" (parts x model) or "auto" (= all
    # devices on the parts axis — today's exact 1-D behavior; a
    # single-device Trainer resolves to 1x1).  model > 1 builds the
    # (parts, model) 2-D mesh: params + Adam moments live
    # model-sharded at rest (parallel.model_shard_spec picks the
    # feature dim), the streamed-head [V, H] handoff is pinned
    # model-sharded, and the 1-D shard_map step bodies are reused
    # unchanged with MODEL_AXIS as a GSPMD auto axis.  Validated by
    # resolve_mesh (the CLI's --mesh routes through it too).
    mesh: Any = "auto"


def resolve_dtypes(name: str):
    """CLI/benchmark dtype-mode string -> ``(dtype, compute_dtype)`` —
    the ONE place the mode names map to TrainConfig fields, so the CLI
    and the benchmarks can never train with different semantics for
    the same flag value."""
    if name == "float32":
        return jnp.float32, None
    if name == "bfloat16":
        return jnp.bfloat16, None
    if name == "mixed":
        return jnp.float32, jnp.bfloat16
    raise ValueError(f"unknown dtype mode {name!r}; expected "
                     "'float32', 'bfloat16', or 'mixed'")


def resolve_prefetch(config: TrainConfig) -> int:
    """``TrainConfig.prefetch`` -> staging-pool depth: 'auto' = 1 (the
    double-buffered default — one block ahead is enough to hide the
    host copy + H2D issue, and deeper pools only add live buffers);
    an int >= 0 is taken literally (0 = synchronous)."""
    p = config.prefetch
    if p == "auto":
        return 1
    try:
        depth = int(p)
    except (TypeError, ValueError):
        raise ValueError(f"unknown prefetch {p!r}; expected 'auto' or "
                         "an int >= 0") from None
    if depth < 0:
        raise ValueError(f"prefetch must be >= 0, got {depth}")
    return depth


# Chunked-head resolution constants: the block matches the streamed
# head's staging granularity (core/streaming.py StreamedHead
# block_rows — the machinery linear_chunked is the in-jit twin of);
# the auto threshold keeps small graphs on the plain matmul (a
# [262k, C] head is the scale where the full-width program starts
# mattering to compile size and the scan adds nothing below it).
HEAD_CHUNK_ROWS = 65_536
HEAD_CHUNK_AUTO_MIN_ROWS = 262_144


def resolve_head_chunk(config: TrainConfig, num_rows: int) -> int:
    """``TrainConfig.head_chunk`` -> the concrete block size the
    GraphContext carries (0 = unchunked).  ONE validator — the CLI
    routes --head-chunk through this same function.  'auto' chunks at
    :data:`HEAD_CHUNK_ROWS` once ``num_rows`` reaches
    :data:`HEAD_CHUNK_AUTO_MIN_ROWS`; an explicit block >= the row
    count degenerates to 0 (a single block would only add scan
    overhead)."""
    hc = config.head_chunk
    if hc == "auto":
        return (HEAD_CHUNK_ROWS
                if num_rows >= HEAD_CHUNK_AUTO_MIN_ROWS else 0)
    try:
        block = int(hc)
    except (TypeError, ValueError):
        raise ValueError(f"unknown head_chunk {hc!r}; expected 'auto' "
                         "or an int >= 0") from None
    if block < 0:
        raise ValueError(f"head_chunk must be >= 0, got {block}")
    return 0 if block >= num_rows else block


def resolve_async_save(config: TrainConfig) -> bool:
    """``TrainConfig.async_save`` -> the concrete saver mode the
    rotation is constructed with.  ONE validator — the CLI routes
    --async-save through this same function.  'auto' enables the
    async saver exactly when the job is single-process (see the
    config field's comment for why multi-process resolves off);
    'on'/'off' (or bools) are literal."""
    v = config.async_save
    if isinstance(v, bool):
        return v
    if v == "on":
        return True
    if v == "off":
        return False
    if v == "auto":
        import jax
        return jax.process_count() == 1
    raise ValueError(f"unknown async_save {v!r}; expected 'auto', "
                     "'on', or 'off'")


def resolve_partition(config: TrainConfig) -> str:
    """``TrainConfig.partition`` -> the concrete split method:
    'auto' resolves to 'cost' (cold-start weights are the quantized
    edge-balance prior, so the searched split is never worse than the
    greedy sweep under the model and usually strictly better on
    skewed graphs).  Unknown values raise — the CLI validates through
    this same function so the vocabularies can never diverge."""
    p = config.partition
    if p == "auto":
        return "cost"
    if p in ("greedy", "cost"):
        return p
    raise ValueError(f"unknown partition {p!r}; expected 'greedy', "
                     "'cost', or 'auto'")


def resolve_mesh(config: TrainConfig,
                 num_parts: Optional[int] = None,
                 num_devices: Optional[int] = None):
    """``TrainConfig.mesh`` -> the concrete ``(parts, model)`` shape.

    'auto' = ``(num_parts or 1, 1)`` — exactly today's 1-D layout (the
    degenerate all-parts shape of ``parallel.candidate_mesh_shapes``).
    A "PxM" string names both axes explicitly; a (p, m) tuple is taken
    literally.  ONE validator — the CLI routes --mesh through this
    same function, and both trainer constructors resolve through it,
    so the vocabularies can never diverge.  When ``num_parts`` is
    given (the DistributedTrainer's positional parts count), an
    explicit P must match it; when ``num_devices`` is given, p*m must
    fit."""
    v = config.mesh
    if v in (None, "auto"):
        p, m = (int(num_parts) if num_parts else 1), 1
    else:
        if isinstance(v, str):
            try:
                ps, ms = v.lower().split("x")
                p, m = int(ps), int(ms)
            except ValueError:
                raise ValueError(
                    f"unknown mesh {v!r}; expected 'auto' or 'PxM' "
                    "(e.g. '2x4')") from None
        else:
            try:
                p, m = (int(v[0]), int(v[1]))
            except (TypeError, ValueError, IndexError):
                raise ValueError(
                    f"unknown mesh {v!r}; expected 'auto', 'PxM', or "
                    "a (parts, model) pair") from None
        if p < 1 or m < 1:
            raise ValueError(f"mesh axes must be >= 1, got {p}x{m}")
        if num_parts is not None and p != int(num_parts):
            raise ValueError(
                f"mesh {p}x{m} names {p} parts but the trainer was "
                f"built with {num_parts} partitions — the parts axis "
                "IS the partition count")
    if num_devices is not None and p * m > int(num_devices):
        raise ValueError(
            f"mesh {p}x{m} needs {p * m} devices, have {num_devices}")
    return p, m


def compute_dtype_of(config: TrainConfig):
    """The activation/feature dtype: ``compute_dtype`` when set (mixed
    precision), else ``dtype``."""
    return (config.compute_dtype if config.compute_dtype is not None
            else config.dtype)


def cast_floats(tree, dtype):
    """Cast floating-point leaves to ``dtype``; integer leaves (masks,
    labels, index tables) pass through.  A no-op cast is left to XLA
    to elide."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


# Attention models switch from the per-width bucket layout to the
# uniform flat8 layout past this edge count: the bucket path's
# Python-unrolled checkpointed scans (one per large width bucket,
# doubled by autodiff) pushed ogbn-products-scale remote compile past
# 40 min (VERDICT r3 missing #3); the flat8 path has ONE scan shape.
ATTN_FLAT8_MIN_EDGES = 20_000_000


def resolve_attention_impl(model, config: TrainConfig,
                           dataset=None) -> TrainConfig:
    """The ONE model-driven impl policy both trainers apply: models
    whose ops need the ELL tables — attention (edge softmax over one
    bucket row, ops/attention.py) and MAX/MIN aggregation (no
    sectioned/bdense form) — get aggr_impl overridden to 'ell'
    with a startup echo, and halo='ring' rejected up front (the ring
    accumulator is additive; failing at jit-trace time would waste
    the whole ring-table build first).  Attention models on graphs
    past ``ATTN_FLAT8_MIN_EDGES`` route to the uniform 'attn_flat8'
    layout instead (compile size at scale; pass ``dataset`` to enable
    the scale check) — additive attention only: a dot-product attention
    model (``transformer_attention``) on that layout, asked for or
    routed to, is refused by name (``TFATTN_FLAT8_REFUSAL``)."""
    if model.uses_dot_attention() and (
            config.aggr_impl == "attn_flat8"
            or (dataset is not None and config.aggr_impl != "ell"
                and dataset.graph.num_edges >= ATTN_FLAT8_MIN_EDGES)):
        from ..models.builder import TFATTN_FLAT8_REFUSAL
        raise NotImplementedError(TFATTN_FLAT8_REFUSAL)
    why = ("attention" if model.uses_attention()
           else "MAX/MIN aggregation" if model.uses_max_aggregation()
           else None)
    if config.aggr_impl == "attn_flat8":
        # validate BEFORE the no-op return: a sum-only model with this
        # impl must fail here, not after the (expensive at 100M+
        # edges) table build inside a jit trace
        if why != "attention":
            raise NotImplementedError(
                "aggr_impl='attn_flat8' is the attention-only layout; "
                f"this model uses {why or 'sum aggregation'}")
    if why is None:
        return config
    if config.halo == "ring":
        raise NotImplementedError(
            f"{why} models are not supported with halo='ring' (the "
            "ring accumulator is additive; the whole neighborhood is "
            "needed per row); use halo='gather'")
    if config.aggr_impl == "attn_flat8":
        return config
    if why == "attention" and dataset is not None and \
            config.aggr_impl != "ell" and \
            dataset.graph.num_edges >= ATTN_FLAT8_MIN_EDGES:
        import dataclasses
        emit("resolve",
             f"aggr_impl={config.aggr_impl!r} -> 'attn_flat8' "
             f"(attention at E={dataset.graph.num_edges:,}: uniform "
             "layout keeps the compile small)",
             requested=config.aggr_impl, resolved="attn_flat8")
        return dataclasses.replace(config, aggr_impl="attn_flat8")
    if config.aggr_impl == "ell":
        return config
    if why == "MAX/MIN aggregation":
        if config.aggr_impl == "segment":
            # _max_fwd has a real segment path (jax.ops.segment_max) —
            # an explicitly requested 'segment' must not be silently
            # overridden (ADVICE r3); only sectioned and bdense lack
            # a MAX form
            return config
        if config.aggr_impl == "flat_sum":
            # the uniform flat layout has a MAX twin
            # (ops/aggregate.py aggregate_flat_max) — an explicit
            # flat_sum stands
            return config
        from ..core.ell import FLAT_SUM_MIN_EDGES
        if dataset is not None and \
                dataset.graph.num_edges >= FLAT_SUM_MIN_EDGES:
            # large MAX graphs get the same uniform-scan consolidation
            # as the sum path: the ELL fallback's per-bucket unroll is
            # exactly the compile wall the flat layout removes
            import dataclasses
            emit("resolve",
                 f"aggr_impl={config.aggr_impl!r} -> 'flat_sum' "
                 f"(MAX/MIN at E={dataset.graph.num_edges:,}: uniform "
                 "layout keeps the compile small)",
                 requested=config.aggr_impl, resolved="flat_sum")
            return dataclasses.replace(config, aggr_impl="flat_sum")
    # echo unconditionally: this changes user-selected behavior, so it
    # must never be silent (ADVICE r3)
    emit("resolve", f"aggr_impl={config.aggr_impl!r} -> 'ell' "
         f"({why} model needs the ELL tables)",
         requested=config.aggr_impl, resolved="ell", why=why)
    import dataclasses
    return dataclasses.replace(config, aggr_impl="ell")


def resolve_fuse(model: Model, config: TrainConfig) -> Model:
    """``aggr_fuse`` resolution — ONE place for the rule (both
    trainers): 'off' leaves the model alone; 'auto'/'on' rewrite the
    fusable ``norm -> aggregate -> norm [-> relu]`` chains into fused
    ops (models/builder.py fuse_norm_aggregate).  Returns the model to
    train — the ORIGINAL object when nothing fused, so callers can
    compare identity.  Parameter names are untouched either way."""
    if config.aggr_fuse == "off":
        return model
    if config.aggr_fuse not in ("auto", "on"):
        raise ValueError(
            f"unknown aggr_fuse {config.aggr_fuse!r}; expected "
            "'auto', 'on', or 'off'")
    fused = model.fuse_norm_aggregate()
    # count NEWLY fused chains: an already-fused model re-entering the
    # resolve pass (resolve_config is idempotent — the program-space
    # auditor asserts it) has fused_aggregate ops but nothing left to
    # rewrite, and must come back as the SAME object with no re-echo
    n = fused.num_fused_aggregates() - model.num_fused_aggregates()
    if n <= 0:
        if config.aggr_fuse == "on" and not model.num_fused_aggregates():
            # an explicit request that changes nothing must say so
            emit("resolve", "aggr_fuse='on': no fusable "
                 "norm->aggregate->norm chain in this model — running "
                 "unfused", fuse=0)
        return model
    emit("resolve", f"aggr_fuse: {n} norm->aggregate->norm chain(s) "
         f"folded into the aggregation", console=config.verbose,
         fuse=n)
    return fused


def _relation_tables(rel_orders, rel_cuts):
    """``(order, rels)`` of every relation table set a typed model's
    two programs scan: the whole one (``rels`` None) of each resolved
    order (``Model.rel_orders()``), then the loss program's cut ones
    (``Model.loss_cut().rel_cuts()``)."""
    return ([(order, None) for order in dict.fromkeys(rel_orders)]
            + list(dict.fromkeys(rel_cuts)))


def _plan_kwargs(model: Model, dataset: Dataset, config: TrainConfig,
                 num_parts: int) -> Dict[str, Any]:
    """What both the autopilot and the resolved-plan echo hand
    core/memory.py: the shapes, the dtypes' sizes, the impl-specific
    table charge and the scan layouts' chunk height."""
    from ..core.ell import scan_chunk_rows
    from ..core.memory import charged_table_bytes
    g = dataset.graph
    rel_bytes = 0
    if model.uses_relations() and dataset.typed is not None:
        # the relation tables of the passes the resolved orders run —
        # the whole ones and the loss program's cut ones — read off
        # the degrees before any table exists: an index and an fp32
        # weight a slot, an output row a sub-row ('segment': the
        # forward edge lists)
        for order, rels in _relation_tables(
                model.rel_orders(), model.loss_cut().rel_cuts()):
            fwd, bwd = ORDER_PASSES[order]
            typed = (dataset.typed if rels is None
                     else dataset.typed.restrict(rels))
            rel_bytes += (12 * typed.num_edges
                          if config.aggr_impl == "segment" else
                          68 * (typed.pass_sub_rows(fwd)
                                + typed.pass_sub_rows(bwd)))
    return dict(
        # the train step is the peak: its op list, each array at the
        # height the loss program gives it (Model.loss_cut)
        num_nodes=g.num_nodes, num_edges=g.num_edges,
        ops=model.loss_cut()._ops,
        num_parts=num_parts,
        scan_rows=(0 if model.uses_attention()
                   or model.uses_max_aggregation()
                   else scan_chunk_rows(config.aggr_impl,
                                        -(-g.num_edges // num_parts))),
        dtype_bytes=jnp.dtype(compute_dtype_of(config)).itemsize,
        param_bytes=jnp.dtype(config.dtype).itemsize,
        extra_table_bytes=rel_bytes + charged_table_bytes(
            config.aggr_impl, model.uses_attention(),
            model.uses_max_aggregation(), config.bdense_a_budget))


def modeled_plan(model: Model, dataset: Dataset, config: TrainConfig,
                 num_parts: int = 1) -> Dict[str, Any]:
    """The memory model's estimate for the RESOLVED config, by
    component, with what each op was charged, remat and the model's
    depth (``core/memory.py describe_plan``) — the run manifest's
    ``memory_plan``.  Its ``est_bytes`` is the number the compile
    observer (obs/compile_watch.py) holds against XLA's actual
    ``memory_analysis()`` so the planner and the residency can never
    silently disagree again (round-5 advisor).  Computed for manual
    configs too: the autopilot only runs under ``memory='auto'``, but
    the modeled-vs-actual delta is evidence on every run."""
    from ..core.memory import describe_plan
    with span("setup.resolve.plan"):
        return describe_plan(
            halo=config.halo if num_parts > 1 else "gather",
            features=config.features, remat=config.remat,
            **_plan_kwargs(model, dataset, config, num_parts))


def resolve_symmetric(dataset: Dataset,
                      symmetric: Optional[bool]) -> bool:
    if symmetric is None:
        from ..core.graph import check_symmetric
        with span("setup.symmetry",
                  edges=dataset.graph.num_edges) as s:
            s["symmetric"] = check_symmetric(dataset.graph)
        return s["symmetric"]
    return symmetric


def apply_memory_autopilot(model: Model, dataset: Dataset,
                           config: TrainConfig,
                           num_parts: int = 1) -> TrainConfig:
    """Resolve ``memory='auto'`` into concrete halo/features/remat via
    core/memory.choose_memory_plan, echoing the decision like the
    reference's startup config print (``gnn.cc:48-60``).  No-op for
    ``memory='manual'``."""
    if config.memory != "auto":
        return config
    import dataclasses
    from ..core.memory import choose_memory_plan
    # bdense keeps an A-table resident next to the model; the resolve
    # pass (resolve_config) runs aggr_impl='auto' (incl. the bdense
    # structure probe) BEFORE this autopilot, so a probe-selected
    # bdense is charged exactly like an explicit one — the planner and
    # the actual residency can no longer disagree by up to the A
    # budget (round-5 advisor).  Attention/MAX models never keep the
    # table: resolve_attention_impl (which runs AFTER the autopilot,
    # because it must see the chosen halo) rewrites their impl away
    # from bdense.  charged_table_bytes (core/memory.py) is the ONE
    # home for the charge rule.
    with span("setup.resolve.plan"):
        plan = choose_memory_plan(
            hbm_bytes=config.hbm_bytes,
            head_streamable=(model.streamable_head() is not None
                             or model.streamable_agg_head() is not None),
            **_plan_kwargs(model, dataset, config, num_parts))
    # a plan that doesn't fit echoes even with verbose off — running
    # anyway is a deliberate gamble the operator must see
    emit("plan", plan.echo(), console=config.verbose or not plan.fits,
         halo=plan.halo, features=plan.features, remat=plan.remat,
         fits=plan.fits, est_bytes=plan.est_bytes,
         budget_bytes=plan.budget_bytes, candidates=plan.candidates)
    return dataclasses.replace(
        config, memory="manual", features=plan.features,
        remat=plan.remat,
        halo=plan.halo if num_parts > 1 else config.halo)


def resolve_auto_impl_probed(graph, out_rows: Optional[int] = None, *,
                             bdense_min_fill: int = 64,
                             bdense_a_budget: Optional[int] = 2 << 30,
                             bdense_group: int = 1,
                             verbose: bool = False,
                             multiprocess: bool = False):
    """ONE home for the full ``aggr_impl='auto'`` rule: the measured
    sectioned/ell node-count window (core/ell.py resolve_auto_impl)
    plus the bdense STRUCTURE probe — when the vertex order
    concentrates enough edges into [128,128] tiles (community graphs
    after ``--reorder lpa``), the MXU block-dense path beats the
    row-rate-bound gather (measured 1.64-2.49x, BASELINE.md).  The
    probe is census-only (~a second at Reddit scale) and native-gated.

    Returns ``(impl, census)``; ``census`` is the reusable
    ``(keys, counts)`` when the probe selected 'bdense' over the SAME
    square tile space plan_blocks will use, else None.

    ``multiprocess=True`` skips the probe entirely: its outcome
    depends on per-host native availability, and every SPMD process
    must resolve the SAME impl — multi-process resolution stays pure
    arithmetic (set aggr_impl explicitly to use bdense there)."""
    from ..core.ell import resolve_auto_impl
    from ..ops import blockdense as _BD
    impl = resolve_auto_impl(graph.num_nodes, out_rows=out_rows,
                             num_edges=graph.num_edges)
    if impl == "flat_sum":
        # the compile-wall route (core/ell.py FLAT_SUM_MIN_EDGES):
        # outside sectioned's measured window at this edge count the
        # per-bucket ELL unroll would compile one program per degree
        # bucket — changes the execution path, so it echoes
        # unconditionally.  Pure arithmetic: multi-process safe.
        emit("resolve", f"aggr_impl='auto' -> 'flat_sum' "
             f"(E={graph.num_edges:,} past the sectioned window: ONE "
             f"uniform scan program instead of one per degree bucket)",
             resolved="flat_sum", num_edges=int(graph.num_edges))
        return impl, None
    if (impl != "sectioned" or multiprocess
            or graph.num_edges < _BD.BDENSE_AUTO_MIN_EDGES):
        return impl, None
    with span("setup.resolve.probe"):
        probe = _BD.probe_dense_frac(
            graph.row_ptr, graph.col_idx, graph.num_nodes,
            min_fill=bdense_min_fill, a_budget_bytes=bdense_a_budget,
            group=bdense_group, return_census=True)
    if probe is None:
        return impl, None
    frac, census = probe
    if frac >= _BD.BDENSE_AUTO_MIN_FRAC:
        # changes the execution path — echoes unconditionally
        emit("resolve", f"aggr_impl='auto' -> 'bdense' (census: "
             f"{frac:.0%} of edges on dense tiles >= "
             f"{_BD.BDENSE_AUTO_MIN_FRAC:.0%})",
             resolved="bdense", dense_frac=round(float(frac), 4))
        return "bdense", census
    emit("resolve", f"auto bdense probe: dense_frac {frac:.1%} < "
         f"{_BD.BDENSE_AUTO_MIN_FRAC:.0%} — staying sectioned",
         console=verbose, resolved=impl,
         dense_frac=round(float(frac), 4))
    return impl, None


def resolve_auto_impl_early(model: Model, config: TrainConfig, graph,
                            out_rows: Optional[int] = None,
                            multiprocess: bool = False):
    """``aggr_impl='auto'`` resolution shared by BOTH trainer
    constructors — ONE home for the rule: the measured window split +
    bdense structure probe run BEFORE the memory autopilot, so a
    probe-selected bdense A-table is charged into the memory plan and
    the remat downgrade applies (round-5 advisor).  Attention/MAX
    models skip (resolve_attention_impl rewrites their impl anyway
    and they never keep the A-table); ``features='host'`` skips (its
    graph tables may never be built — the placeholder/late path
    resolves lazily, and paying the ~1 s census for it would be pure
    startup cost).  Returns ``(config, census)``."""
    if config.aggr_impl != "auto" or config.features == "host" \
            or model.uses_attention() or model.uses_max_aggregation():
        return config, None
    impl, census = resolve_auto_impl_probed(
        graph, out_rows=out_rows,
        bdense_min_fill=config.bdense_min_fill,
        bdense_a_budget=config.bdense_a_budget,
        bdense_group=config.bdense_group,
        verbose=config.verbose,
        multiprocess=multiprocess)
    return dc_replace(config, aggr_impl=impl), census


def resolve_relations(model: Model, dataset: Dataset,
                      config: TrainConfig, num_parts: int = 1):
    """The typed-graph half of the resolve pass (no-op for every
    other family): the relations read off the graph
    (``core/relations.py derive_typed``, kept on ``dataset.typed``)
    must be the ones the model was built for; ``aggr_impl='auto'``
    resolves to 'flat_sum' — the one table layout the relation
    aggregation has (its two index spaces are one global section
    each; 'segment' stays the edge-list reference) — and each layer's
    product goes to the side of its mean that gathers the narrower
    rows at the width that layout runs them
    (``resolve_rel_order``), echoed like every other resolution."""
    if not model.uses_relations():
        return model, config
    if num_parts > 1:
        raise NotImplementedError(
            "a typed graph (--model rgcn) runs on one chip: the vertex "
            "partitioner (core/partition.py) and the distributed step "
            "(parallel/distributed.py) know one index space and no "
            "relation tables; use --parts 1")
    from ..core.ell import agg_lane_width
    from ..core.relations import derive_typed, resolve_rel_order
    if dataset.typed is None:
        dataset.typed = derive_typed(dataset.graph,
                                     model.typed["node_types"])
    if (dataset.typed.node_types != model.typed["node_types"]
            or dataset.typed.relations != model.typed["relations"]):
        raise ValueError(
            f"the model was built for kinds "
            f"{list(model.typed['node_types'])} and relations "
            f"{list(model.typed['relations'])}; the graph holds "
            f"{list(dataset.typed.node_types)} and "
            f"{list(dataset.typed.relations)}")
    impl = config.aggr_impl
    if impl == "auto":
        impl = "flat_sum"
        emit("resolve", "aggr_impl='auto' -> 'flat_sum' (typed graph: "
             "one uniform scan over the stacked relation tables)",
             console=config.verbose, resolved="flat_sum",
             num_edges=int(dataset.typed.num_edges))
    elif impl not in ("segment", "flat_sum"):
        raise NotImplementedError(
            f"the relation aggregation has no {impl!r} layout; --impl "
            f"takes auto, flat_sum or segment for --model rgcn")
    config = dc_replace(config, aggr_impl=impl)
    resolved = model.with_rel_orders(
        lambda i, o: resolve_rel_order(
            i, o, lambda f: agg_lane_width(f, impl, "gather")))
    if resolved is not model:
        emit("resolve", "rel_order: " + ", ".join(
            f"layer {l} {o}" for l, o in enumerate(
                resolved.rel_orders())),
            console=config.verbose, rel_order=list(resolved.rel_orders()))
    return resolved, config


def resolve_agg_orders(model: Model, dataset: Dataset,
                       config: TrainConfig, num_parts: int = 1) -> Model:
    """Each GCN-form layer's normalized sum on the side of its
    bias-free linear that ``core/relations.py resolve_agg_order``
    names (``Model.with_agg_order``), read off the layer's widths, the
    lanes the resolved layout runs them at and whether a parameter
    precedes the layer's input.  Returns the SAME model when nothing
    moves.

    Not where the host tier streams the input head out of core
    (``features='host'``, ``core/streaming.py StreamedHead``): it
    streams ``dropout -> linear`` from the features (or after a
    parameter-free prefix), and a sum moved between the two leaves no
    head to stream.  Under ``memory='auto'`` the autopilot picks that
    tier only where nothing else fits, so the sums move only where the
    rewritten model fits the budget without it; elsewhere the
    reference's order stays, and the autopilot plans that (its head
    streamed, or under remat, whose runs the rewrite also moves)."""
    from ..core.ell import agg_lane_width
    # 'auto' is left here only to the attention and MAX models, whose
    # layout resolve_attention_impl settles after the plan
    if config.features == "host" or config.aggr_impl == "auto":
        return model
    halo = config.halo if num_parts > 1 else "gather"
    ordered = model.with_agg_order(
        lambda i, o, trained: resolve_agg_order(
            i, o, trained,
            lambda f: agg_lane_width(f, config.aggr_impl, halo)))
    if ordered is model:
        return model
    if config.memory == "auto":
        from ..core.memory import choose_memory_plan
        with span("setup.resolve.plan"):
            fits = choose_memory_plan(
                hbm_bytes=config.hbm_bytes, head_streamable=False,
                **_plan_kwargs(ordered, dataset, config, num_parts)).fits
        if not fits:
            return model
    return ordered


def agg_order_plan(model: Model, aggr_impl: str,
                   halo: str = "gather") -> Dict[str, Any]:
    """The ``plan`` line's ``agg_order``: one ``[op, order, in_lanes,
    out_lanes, passes]`` a GCN-form layer (``Model.gcn_layers``) — the
    op that sums, the side of the linear it runs on
    (``resolve_agg_orders``), the lanes the linear's input and output
    widths run at in the sum scan (``core/ell.py agg_lane_width``) and
    the sum passes a train step makes for the layer
    (``core/relations.py agg_passes``).  Empty for a model without
    one."""
    from ..core.ell import agg_lane_width
    rows = [[l.sum_op, l.order, agg_lane_width(l.in_dim, aggr_impl, halo),
             agg_lane_width(l.out_dim, aggr_impl, halo),
             agg_passes(l.order, l.trained)]
            for l in model.gcn_layers()]
    return {"agg_order": rows} if rows else {}


def resolve_config(model: Model, dataset: Dataset, config: TrainConfig,
                   num_parts: int = 1, multiprocess: bool = False):
    """THE config resolve pass — fuse rewrite, ``aggr_impl='auto'``
    (incl. the bdense structure probe), the side of each GCN-form
    layer's linear its sum runs on, memory autopilot, attention
    impl — in the ONE order that makes the memory plan honest: the
    probe runs first so an auto→bdense outcome re-enters
    ``choose_memory_plan`` with the A-budget charged
    (``core/memory.charged_table_bytes``), the sums move before the
    plan so it charges the ops that run, and the attention rewrite
    runs last because it must see the chosen halo.  Shared by BOTH
    trainer constructors and the program-space auditor
    (``analysis/programspace.py``) so the statically enumerated
    program space and the programs the trainers actually build can
    never diverge at the resolve layer.

    Idempotent by construction: a resolved config re-entering this
    pass is unchanged (fuse finds no new chains on a fused model, no
    moved sum is a ``linear_first`` layer any more, ``memory`` is
    already 'manual', ``aggr_impl`` concrete), so
    re-resolving yields the identical program-key set — the auditor
    asserts exactly that (tests/test_programspace.py).

    Returns ``(model, config, bd_census)``."""
    with span("setup.resolve"):
        model = resolve_fuse(model, config)
        with span("setup.resolve.relations"):
            model, config = resolve_relations(model, dataset, config,
                                              num_parts)
        out_rows = (-(-dataset.graph.num_nodes // num_parts)
                    if num_parts > 1 else None)
        config, bd_census = resolve_auto_impl_early(
            model, config, dataset.graph, out_rows=out_rows,
            multiprocess=multiprocess)
        ordered = resolve_agg_orders(model, dataset, config, num_parts)
        planned = apply_memory_autopilot(ordered, dataset, config,
                                         num_parts=num_parts)
        if planned.halo != config.halo:
            # the rule read the lanes of the halo asked for; the ring
            # the plan chose runs every width unpadded
            ordered = resolve_agg_orders(model, dataset, planned,
                                         num_parts)
        if ordered is not model:
            moved = [l.sum_op for l in ordered.gcn_layers()
                     if l.order == AGGREGATE_FIRST]
            emit("resolve", "agg_order: the sum of op(s) "
                 + ", ".join(map(str, moved)) + " runs before its "
                 "linear", console=config.verbose, agg_order=moved)
        model, config = ordered, planned
        config = resolve_attention_impl(model, config, dataset)
    return model, config, bd_census


def make_graph_context(dataset: Dataset, aggr_impl: str = "segment",
                       chunk: int = 512,
                       symmetric: Optional[bool] = None,
                       sect_sub_w: int = 8,
                       sect_u16: bool = False,
                       bdense_min_fill: int = 64,
                       bdense_a_budget: Optional[int] = 2 << 30,
                       bdense_group: int = 1,
                       verbose: bool = False,
                       fuse: bool = False,
                       bd_census=None,
                       head_chunk: int = 0,
                       rel_orders=(), rel_cuts=()) -> GraphContext:
    """Single-device GraphContext: edges padded to the chunk multiple,
    dummy source id == num_nodes (the appended zero row).
    ``sect_sub_w``/``sect_u16`` tune the sectioned layout and
    ``bdense_min_fill`` the block-dense split (TrainConfig fields of
    the same names); ``verbose`` gates the informational echoes (the
    impl-override ones stay unconditional).

    ``fuse=True`` additionally bakes the symmetric ``D^-1/2`` scales
    into the tables (fused-aggregation weight tables / bdense tile
    scales) for models rewritten by ``Model.fuse_norm_aggregate``;
    ``bd_census`` reuses a probe census from an earlier
    :func:`resolve_auto_impl_probed` call (the trainers resolve
    'auto' before the memory autopilot and pass it through).

    ``rel_orders`` (a typed model's ``Model.rel_orders()``): build the
    relation tables of ``dataset.typed`` for the passes those orders
    run, and none of the homogeneous tables; ``rel_cuts`` (its
    ``Model.loss_cut().rel_cuts()``): beside them the tables of the
    relation subsets the loss program's cut layers sum."""
    g = dataset.graph
    view = View.one_device(g, chunk)
    relations = None
    if rel_orders:
        relations = (dataset.typed, _relation_tables(rel_orders,
                                                      rel_cuts))
    elif aggr_impl == "auto":
        aggr_impl, bd_census = resolve_auto_impl_probed(
            g, bdense_min_fill=bdense_min_fill,
            bdense_a_budget=bdense_a_budget,
            bdense_group=bdense_group, verbose=verbose)
    notes: Dict[str, Any] = {}
    tables = build_tables(
        aggr_impl, view, fuse=fuse, relations=relations, notes=notes,
        sect_sub_w=sect_sub_w, sect_u16=sect_u16,
        bdense_min_fill=bdense_min_fill,
        bdense_a_budget=bdense_a_budget, bdense_group=bdense_group,
        census=bd_census)
    if "bd_occupancy" in notes:
        _echo_bdense(tables, notes["bd_occupancy"][0], bdense_min_fill,
                     verbose)
    return GraphContext(
        tables=tables,
        in_degree=upload(g.in_degree, "tables"),
        num_rows=g.num_nodes,
        gathered_rows=g.num_nodes,
        aggr_impl=aggr_impl,
        # every backward pass of a typed graph has its transposed
        # table: the union's symmetry is never read
        symmetric=(True if relations
                   else resolve_symmetric(dataset, symmetric)),
        head_chunk=head_chunk)


def _echo_bdense(tables, occ, min_fill: int, verbose: bool) -> None:
    """The block-dense plan's occupancy (the echo that makes a mis-fit
    choice visible); a plan with no tile changes the path run and
    echoes unconditionally."""
    from ..ops.blockdense import BLOCK
    if tables.a is not None:
        packed = tables.a.shape[-1] == BLOCK // 2
        emit("plan", f"bdense plan: {occ['n_blocks']} blocks, "
             f"fill {occ['mean_fill']}, dense "
             f"{occ['dense_frac']:.0%} (residual "
             f"{1 - occ['dense_frac']:.0%} via sectioned"
             f"{', A u4-packed' if packed else ''})",
             console=verbose, packed=packed, **occ)
    else:
        # no tile qualifies: running the zero-block kernel every step
        # would be pure overhead
        emit("plan", f"bdense: no [128,128] tile reaches min_fill="
             f"{min_fill} on this graph/order — running "
             f"the sectioned residual only", **occ)


def batch_norm_plan(ops, num_nodes: int) -> Dict[str, Any]:
    """The ``batch_norm`` entry of the run manifest's ``resolved``:
    how many such ops, their width, the rows their moments count (the
    real vertices of every partition, never padding) and the bytes of
    running statistics that travel with the parameters.  Empty for a
    model without the op."""
    bns = [(i, op) for i, op in enumerate(ops)
           if op.kind == "batch_norm"]
    if not bns:
        return {}
    return {"batch_norm": {
        "ops": [i for i, _ in bns], "count": len(bns),
        "width": max(op.dim for _, op in bns),
        "rows_counted": int(num_nodes),
        "eps": bns[0][1].attrs["eps"],
        "momentum": bns[0][1].attrs["momentum"],
        "moments": "float32 sums of x and x*x, one pass",
        "stats_bytes": sum(2 * op.dim * 4 for _, op in bns)}}


def model_features(model: Model, dataset: Dataset) -> np.ndarray:
    """The feature rows the model's input holds: all of them, or for a
    typed model the rows of the kinds that have any (kind order), the
    trainable kinds' rows left on the host."""
    if not model.typed:
        return dataset.features
    off = np.concatenate([[0], np.cumsum(model.typed["node_types"])])
    keep = [np.arange(off[k], off[k + 1])
            for k in range(len(off) - 1)
            if k not in model.typed["embed_types"]]
    return np.asarray(dataset.features)[np.concatenate(keep)]


def split_state(params, names):
    """``(trainable, state)``: the parameter dict without and with only
    the entries ``names`` (``Model.state_names()``: the batch-norm
    running statistics).  The optimizer, the gradient and the
    compute-dtype cast see ``trainable`` alone; a step merges the new
    state back (``{**trainable, **state}``).  With no ``names`` the
    first is ``params`` itself."""
    if not names:
        return params, {}
    return ({k: v for k, v in params.items() if k not in names},
            {k: params[k] for k in names})


def cast_compute(params, dtype):
    """:func:`cast_floats` over a parameter dict, leaving a
    ``batch_norm``'s entries (``bn_*``: scale, shift and the running
    statistics) and a ``layer_norm``'s (``ln_*``: scale, shift) in
    float32: they are ``[F]`` vectors read by float32 arithmetic, and a
    bfloat16 copy would only round them."""
    from ..obs.scopes import FLOAT32_PARAM_PREFIXES as kept
    if not isinstance(params, dict) or not any(
            k.startswith(kept) for k in params):
        return cast_floats(params, dtype)
    return {k: (v if k.startswith(kept) else cast_floats(v, dtype))
            for k, v in params.items()}


def cast_params(params, dtype):
    """The step's compute-dtype copy of the parameters, under
    ``roc.opt``; a typed model's embedding tables under
    ``roc.opt.embed`` inside it, so their stream can be told from the
    weights' (obs/scopes.py); a ``batch_norm``'s entries stay float32
    (:func:`cast_compute`)."""
    from ..obs.scopes import EMBED_PARAM_PREFIX, OPT_EMBED_SCOPE
    with jax.named_scope(OPT_SCOPE):
        if not any(k.startswith(EMBED_PARAM_PREFIX) for k in params):
            return cast_compute(params, dtype)
        out = {}
        for k, v in params.items():
            if k.startswith(EMBED_PARAM_PREFIX):
                with jax.named_scope(OPT_EMBED_SCOPE):
                    out[k] = cast_floats(v, dtype)
            else:
                out[k] = cast_floats(v, dtype)
        return out


class Trainer:
    """Owns params + optimizer state and the jitted step functions."""

    def __init__(self, model: Model, dataset: Dataset,
                 config: TrainConfig = TrainConfig()):
        model, config, bd_census = resolve_config(model, dataset,
                                                  config)
        self.model = model
        self.config = config
        self.compute = compute_dtype_of(config)
        self.epoch = 0
        # observability: edge count for edges/sec and the memory
        # model's estimate the compile observer checks XLA against
        self._obs_edges = int(dataset.graph.num_edges)
        with span("setup.resolve"):
            self._plan = modeled_plan(model, dataset, config)
        self._modeled_bytes = self._plan["est_bytes"]
        # dataset identity for the checkpoint config fingerprint
        # (utils/checkpoint.trainer_fingerprint strict half)
        self._fp_dataset = {"V": int(dataset.graph.num_nodes),
                            "E": int(dataset.graph.num_edges)}
        self.labels = upload(dataset.labels, "labels")
        self.mask = upload(dataset.mask, "mask")
        self.adam_cfg = AdamConfig(weight_decay=config.weight_decay)
        # (parts, model) mesh knob: a single-device Trainer hosts only
        # the model axis (parts is always 1 here — partitioning is the
        # DistributedTrainer's job).  model > 1 places params + Adam
        # moments model-sharded at rest (put_replicated picks the dim
        # via parallel.model_shard_spec); the plain jitted steps then
        # inherit the layout through GSPMD (computation follows data),
        # and the streamed-head [V, H] handoff is pinned via
        # _pin_stream.
        _, self._mesh_model = resolve_mesh(
            config, num_parts=1, num_devices=len(jax.devices()))
        self.mesh = None
        with span("setup.params") as s:
            key = jax.random.PRNGKey(config.seed)
            self.key, init_key = jax.random.split(key)
            self.params = model.init_params(init_key, dtype=config.dtype)
            self.opt_state = adam_init(
                split_state(self.params, model.state_names())[0])
            if self._mesh_model > 1:
                from ..parallel.distributed import (make_mesh,
                                                    put_replicated)
                self.mesh = make_mesh(1, model=self._mesh_model)
                self.params = put_replicated(self.params, self.mesh)
                self.opt_state = put_replicated(self.opt_state,
                                                self.mesh)
            s["param_bytes"] = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        self._head = None
        self._head_chunk = resolve_head_chunk(
            config, dataset.graph.num_nodes)
        if config.features == "host" and model.state_names():
            raise NotImplementedError(
                "features='host' streams a stateless head: a model with "
                "batch statistics (batch_norm) trains with "
                "features='hbm'")
        if config.features == "host":
            # host-resident features streamed through the first layer
            # (the reference's ZC tier, types.cu:22-32)
            head = model.streamable_head()
            prefix_ops = None
            if head is None:
                # second shape the tier serves: a parameter-free
                # aggregation prefix (SGC family) evaluated ONCE fully
                # out-of-core, then the same streamed dropout/linear
                agg = model.streamable_agg_head()
                if agg is None:
                    raise NotImplementedError(
                        "features='host' needs a streamable model head "
                        "(input -> dropout -> linear, Model."
                        "streamable_head) or an aggregation-prefix "
                        "head (norm/aggregate chain -> dropout -> "
                        "linear, Model.streamable_agg_head).  This "
                        "model's first layer consumes raw features "
                        "elsewhere — use features='hbm', or partition "
                        "with --parts/halo='ring' to shrink per-device "
                        "residency")
                (prefix_ops, rate, self._head_param,
                 self._tail_model) = agg
            else:
                rate, self._head_param, self._tail_model = head
            from ..core.streaming import StreamedHead
            depth = resolve_prefetch(config)
            self._head = StreamedHead(rate, prefetch=depth)
            with span("setup.tables", table="feats_host"):
                feats_np = np.asarray(dataset.features)
                if prefix_ops is not None:
                    from ..core.streaming import stream_prefix_to_host
                    feats_np = stream_prefix_to_host(
                        dataset.graph, prefix_ops, feats_np,
                        prefetch=depth)
                # host copy in the COMPUTE dtype (ml_dtypes bf16 under
                # mixed): device_put then ships 2-byte blocks — the
                # host-link transfer is this tier's dominant per-epoch
                # cost, so staging fp32 and casting on device would
                # forfeit half the mode's bandwidth win
                self.feats_host = np.ascontiguousarray(
                    feats_np.astype(jnp.dtype(self.compute), copy=False))
            self.feats = None
            from ..obs.compile_watch import ObservedJit
            with span("setup.steps"):
                # y (arg 1) is donated: the projected [V, H] activation
                # is rebuilt by the streamed head every step and never
                # read after this call — undonated it doubled its
                # residency across the tail (found by roc-lint
                # jaxpr-non-donated)
                self._tail_grad = ObservedJit(
                    self._tail_grad_impl, name="tail_grad",
                    donate_argnums=(1,),
                    modeled_bytes=self._modeled_bytes,
                    verbose=config.verbose)
                self._tail_eval = ObservedJit(self._tail_eval_impl,
                                              name="tail_eval",
                                              verbose=config.verbose)
                # grads (arg 2) are donated too: they are rebuilt every
                # step and never read after the update — undonated
                # they'd hold a param-sized buffer alive across the
                # whole apply (found by roc-lint jaxpr-non-donated)
                self._apply_update = ObservedJit(
                    self._apply_update_impl, name="apply_update",
                    donate_argnums=(0, 1, 2), verbose=config.verbose)
        else:
            with span("setup.upload", what="features") as s:
                self.feats = jnp.asarray(model_features(model, dataset),
                                         dtype=self.compute)
                s["h2d_bytes"] = self.feats.nbytes
        if self._head is not None and not any(
                op.kind in ("scatter_gather", "gat", "fused_aggregate")
                for op in self._tail_model._ops):
            # the model's whole graph part ran in the host-side
            # precompute (SGC): don't build O(E) tables nobody reads
            from ..models.builder import GraphContext
            g = dataset.graph
            self.gctx = GraphContext(
                tables=EdgeList(src=jnp.zeros(1, jnp.int32),
                                dst=jnp.zeros(1, jnp.int32)),
                in_degree=upload(g.in_degree, "tables"),
                num_rows=g.num_nodes, gathered_rows=g.num_nodes,
                aggr_impl="segment",
                head_chunk=self._head_chunk,
                # only the scatter_gather VJP reads symmetric, and this
                # branch is taken only when the tail has none — a
                # constant avoids check_symmetric's O(E log E) sort
                symmetric=True)
        else:
            self.gctx = make_graph_context(
                dataset, config.aggr_impl, config.chunk,
                symmetric=config.symmetric,
                sect_sub_w=config.sect_sub_w,
                sect_u16=config.sect_u16,
                bdense_min_fill=config.bdense_min_fill,
                bdense_a_budget=config.bdense_a_budget,
                bdense_group=config.bdense_group,
                verbose=config.verbose,
                fuse=model.num_fused_aggregates() > 0,
                bd_census=bd_census,
                head_chunk=self._head_chunk,
                rel_orders=model.rel_orders(),
                rel_cuts=model.loss_cut().rel_cuts())
            if config.aggr_impl == "auto":
                # attention/MAX models reach here with 'auto' already
                # rewritten by resolve_attention_impl; any other
                # residue resolves inside make_graph_context — reflect
                # it so artifacts record what actually runs
                self.config = dc_replace(self.config,
                                         aggr_impl=self.gctx.aggr_impl)
        # Dataset tensors are jitted *arguments*, not closure captures:
        # capturing them would embed a second copy of the feature matrix
        # as an executable constant and recompile per Trainer instance
        # (the Reddit feature matrix alone is ~560 MB).  Only params +
        # opt state are donated — the data args are reused every step.
        # ObservedJit records lower/compile wall time + XLA cost/memory
        # introspection on the first call (obs/compile_watch.py).
        from ..obs.compile_watch import ObservedJit
        with span("setup.steps"):
            self._train_step = ObservedJit(
                self._train_step_impl, name="train_step",
                donate_argnums=(0, 1),
                modeled_bytes=self._modeled_bytes,
                verbose=config.verbose)
            # eval and predict share ONE compiled program: the eval
            # step returns (metrics, logits) — the logits already exist
            # inside the step, so outputting them costs one [V, C]
            # buffer write per eval while removing a whole compiled
            # program from every config's space (program-space
            # consolidation, ISSUE 7; evaluate() fetches only the
            # metrics leaf)
            self._eval_step = ObservedJit(self._eval_step_impl,
                                          name="eval_step",
                                          verbose=config.verbose)
        from ..obs.manifest import run_manifest
        with span("setup.manifest"):
            run_manifest(
                config=self.config, dataset=dataset, model=model,
                extra={"modeled_step_bytes": self._modeled_bytes},
                agg_window={
                    **self.gctx.agg_window(
                        model._ops, edges=int(dataset.graph.num_edges),
                        compute=self.compute),
                    **self.gctx.attention_plan(model._ops),
                    **self.gctx.relation_plan(
                        model._ops, dataset.typed,
                        model.loss_cut()._ops),
                    **self.gctx.soft_plan(model._ops, self.compute),
                    **batch_norm_plan(model._ops,
                                      dataset.graph.num_nodes),
                    **agg_order_plan(model, self.gctx.aggr_impl,
                                     self.gctx.halo),
                    "memory_plan": self._plan},
                console=config.verbose)
        from ..utils.profiling import EpochTimer, MetricsLog
        # annotate=True routes every phase span through
        # jax.profiler.TraceAnnotation so a --profile-dir trace's host
        # plane carries the same named phases as the timeline lanes
        self.timer = EpochTimer(
            annotate=bool(config.profile_dir))
        self.metrics_log = MetricsLog(config.metrics_path)
        # set-up's spans, cli.main's among them, as one batch
        flush_spans("setup")

    def _train_step_impl(self, params, opt_state, key, lr, feats,
                         labels, mask, gctx):
        # gctx arrives as a jit ARGUMENT (GraphContext is a pytree):
        # closure-capturing it would embed the edge/ELL tables as HLO
        # constants — see the register_pytree_node note in builder.py
        # the running statistics ride in ``params`` but are no
        # parameters: the gradient, Adam and the cast see the rest
        params, state = split_state(params, self.model.state_names())

        def objective(p):
            # mixed precision: compute in self.compute; the astype vjp
            # returns fp32 cotangents, so grads/Adam stay in dtype
            p = {**cast_params(p, self.compute), **state}
            return self.model.loss_and_state(
                p, feats, labels, mask, gctx, key=key,
                remat=self.config.remat)
        (loss, state), grads = jax.value_and_grad(
            objective, has_aux=True)(params)
        with jax.named_scope(OPT_SCOPE):
            params, opt_state = adam_update(params, grads, opt_state,
                                            lr, self.adam_cfg)
        return {**params, **state}, opt_state, loss

    def _eval_step_impl(self, params, feats, labels, mask, gctx):
        params = cast_params(params, self.compute)
        logits = self.model.apply(params, feats, gctx, key=None,
                                  train=False)
        with jax.named_scope(LOSS_SCOPE):
            return perf_metrics(
                *self.model.labelled(logits, labels, mask)), logits

    # ---- host-feature streaming path (config.features == "host") ----

    def _tail_grad_impl(self, params, y, key, labels, mask, gctx):
        """Loss + grads of the device-resident tail w.r.t. (params, Y);
        dY feeds the streamed head weight gradient."""
        def objective(p, yy):
            with jax.named_scope(OPT_SCOPE):
                p = cast_floats(p, self.compute)
            loss, _ = self._tail_model.loss_fn(
                p, yy, labels, mask, gctx, key=key, train=True,
                remat=self.config.remat)
            return loss
        loss, (gp, gy) = jax.value_and_grad(objective, argnums=(0, 1))(
            params, y)
        return loss, gp, gy

    def _tail_eval_impl(self, params, y, labels, mask, gctx):
        # (metrics, logits) like _eval_step_impl: the streamed tier's
        # predict reuses this one compiled program (no tail_predict)
        with jax.named_scope(OPT_SCOPE):
            params = cast_floats(params, self.compute)
        logits = self._tail_model.apply(params, y, gctx, key=None,
                                        train=False)
        with jax.named_scope(LOSS_SCOPE):
            return perf_metrics(logits, labels, mask), logits

    def _apply_update_impl(self, params, opt_state, grads, lr):
        with jax.named_scope(OPT_SCOPE):
            return adam_update(params, grads, opt_state, lr,
                               self.adam_cfg)

    def _pin_stream(self, y):
        """Model-shard the streamed-head [V, H] handoff: under a
        model mesh the block-assembled Y would otherwise land fully
        replicated (it is built by per-block device_puts outside any
        jit) and sit at the top of the replication ledger.  One
        device_put re-lays it out H-sharded; the tail programs then
        consume it sharded (GSPMD).  No-op on the 1-D mesh or when H
        does not divide."""
        if self.mesh is None:
            return y
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel import model_shard_spec
        spec = model_shard_spec(y.shape, self._mesh_model)
        if spec is None:
            return y
        return jax.device_put(
            y, NamedSharding(self.mesh, PartitionSpec(*spec)))

    def _streamed_step(self, step_key, lr):
        head_key, tail_key = jax.random.split(step_key)
        # cast the master weight to the compute dtype so the streamed
        # blocks (and Y, hence the whole tail) run in compute precision
        # — the footprint the memory autopilot sized the plan with.
        # The phase spans record host wall time per sub-phase WITHOUT
        # extra barriers (the streamed head is already host-paced per
        # block; a per-phase fetch would serialize the tail dispatch).
        timer = self.timer
        w0 = self.params[self._head_param].astype(self.compute)
        with timer.span("head_forward"):
            y = self._pin_stream(
                self._head.forward(w0, self.feats_host, head_key, True))
        with timer.span("tail_grad"):
            _, grads, gy = self._tail_grad(self.params, y, tail_key,
                                           self.labels, self.mask,
                                           self.gctx)
        with timer.span("head_wgrad"):
            grads[self._head_param] = self._head.wgrad(
                self.feats_host, gy, head_key, True
            ).astype(self.params[self._head_param].dtype)
        with timer.span("update"):
            self.params, self.opt_state = self._apply_update(
                self.params, self.opt_state, grads, lr)

    def pipeline_fields(self) -> Dict[str, float]:
        """Streaming-pipeline metrics accumulated since the last call
        (the staging pool's per-block series), folded into the epoch
        record by ``run_epoch_loop``: ``overlap_frac`` = fraction of
        staging latency hidden under compute (0 for the synchronous
        ``prefetch=0`` path by construction), ``h2d_wait_p50_ms`` =
        median consumer-side stall per block, ``prefetch_depth`` = the
        resolved pool depth.  The per-block waits also land in the
        ``h2d_wait``/``h2d_stage`` timer spans so the report's phase
        table shows them next to the epoch phases."""
        if self._head is None:
            return {}
        stats = self._head.pool.take_stats()
        if not stats["n"]:
            return {}
        self.timer.spans_ms.setdefault("h2d_wait", []).extend(
            stats["wait_ms"])
        self.timer.spans_ms.setdefault("h2d_stage", []).extend(
            stats["stage_ms"])
        # per-block records for the timeline merger's h2d lane (the
        # pool stamps monotonic starts alongside each series)
        self.timer.timeline.extend(
            ("h2d_wait", t0, ms) for t0, ms in
            zip(stats["wait_t0"], stats["wait_ms"]))
        self.timer.timeline.extend(
            ("h2d_stage", t0, ms) for t0, ms in
            zip(stats["stage_t0"], stats["stage_ms"]))
        out: Dict[str, float] = {
            "prefetch_depth": int(stats["depth"]),
            "h2d_wait_p50_ms": stats["wait_p50_ms"],
            "h2d_stage_p50_ms": stats["stage_p50_ms"],
        }
        if stats["overlap_frac"] is not None:
            out["overlap_frac"] = stats["overlap_frac"]
        emit("pipeline", f"h2d: {stats['n']} blocks, wait p50 "
             f"{out['h2d_wait_p50_ms']:.2f} ms, overlap_frac "
             f"{out.get('overlap_frac', 0.0)}", console=False, **out)
        return out

    # ---- loop ----

    def train(self, epochs: Optional[int] = None) -> List[Dict[str, float]]:
        """Run ``epochs`` more epochs; the epoch counter persists across
        calls so lr decay and the eval cadence continue correctly."""
        def do_step(step_key, lr):
            if self._head is not None:
                self._streamed_step(step_key, lr)
                return
            self.params, self.opt_state, _ = self._train_step(
                self.params, self.opt_state, step_key, lr, self.feats,
                self.labels, self.mask, self.gctx)

        return run_epoch_loop(self, epochs, do_step, self.evaluate)

    def sync(self) -> None:
        """Block until all dispatched train steps have finished."""
        from ..utils.profiling import sync
        sync(self.params)

    def predict(self, node_ids=None) -> jax.Array:
        """[V, C] inference-mode logits (the tensor the reference only
        ever reduces to metrics, softmax_kernel.cu:41-79 — exposed so
        a user can export predictions).  Runs the EVAL program and
        takes its logits output — predict compiles nothing of its own
        (program-space consolidation: one compiled program serves
        evaluate and predict; still jitted, so the eager interpreter
        never holds every intermediate activation alive).

        ``node_ids`` gathers a ``[len(ids), C]`` row subset ON DEVICE
        — the full ``[V, C]`` tensor never crosses device→host, which
        is the transfer the serve tier's gather path exists to avoid
        (the eager ``take`` is a tiny per-shape program outside the
        audited step set, same class as the epoch loop's scalar
        ops)."""
        if self._head is not None:
            w0 = self.params[self._head_param].astype(self.compute)
            y = self._pin_stream(
                self._head.forward(w0, self.feats_host, None, False))
            _, logits = self._tail_eval(self.params, y, self.labels,
                                        self.mask, self.gctx)
        else:
            _, logits = self._eval_step(self.params, self.feats,
                                        self.labels, self.mask,
                                        self.gctx)
        if node_ids is None:
            return logits
        ids = np.asarray(node_ids, dtype=np.int32).ravel()
        V = int(logits.shape[0])
        if ids.size and (ids.min() < 0 or ids.max() >= V):
            # jnp.take's out-of-bounds mode is 'fill' (silent NaN
            # rows) — raise like DistributedTrainer/Predictor do, one
            # contract across the serve gather paths
            raise ValueError(f"node ids out of range [0, {V})")
        return jnp.take(logits, jnp.asarray(ids), axis=0)

    def evaluate(self) -> Dict[str, float]:
        # fetch ONLY the metrics leaf: the shared eval/predict program
        # also outputs the [V, C] logits, which must stay on device
        # during training evals
        if self._head is not None:
            w0 = self.params[self._head_param].astype(self.compute)
            y = self._pin_stream(
                self._head.forward(w0, self.feats_host, None, False))
            m, _ = self._tail_eval(self.params, y, self.labels,
                                   self.mask, self.gctx)
            return summarize_metrics(jax.device_get(m))
        m, _ = self._eval_step(self.params, self.feats, self.labels,
                               self.mask, self.gctx)
        return summarize_metrics(jax.device_get(m))


def run_epoch_loop(tr, epochs: Optional[int], do_step,
                   do_eval) -> List[Dict[str, float]]:
    """The reference epoch loop (``gnn.cc:99-111``), shared by the
    single-device and distributed trainers: staircase lr decay,
    async-dispatched train step, every-``eval_every``-epoch eval with
    metrics logging and honest timing.

    ``tr`` provides config/epoch/key/timer/metrics_log/sync state;
    ``do_step(step_key, lr)`` runs one training step (async);
    ``do_eval()`` returns the summarized metrics dict.

    Timing: train steps dispatch asynchronously; before each eval the
    loop blocks on ``tr.sync()`` so ``epoch_ms`` is pure train-step
    wall clock divided by the steps in the burst, and ``eval_ms`` is
    the eval pass (device fetch included) timed separately — eval and
    host overhead no longer fold into the per-epoch number.  The very
    first step of a fresh trainer is the compile step: it is barriered
    and recorded on its own (``m["compile_ms"]`` of the first eval /
    the timer's warmup lap) so every reported ``epoch_ms`` is a steady
    lap — no counter surgery needed downstream.  Evals land on
    ``epoch % eval_every == eval_every - 1`` so each covers a full
    burst of steady steps (the reference prints every 5th epoch,
    ``gnn.cc:107-110``; same cadence, phase-shifted off the compile
    epoch)."""
    from ..obs.heartbeat import Heartbeat
    from ..resilience import inject, preempt
    from ..utils.profiling import trace
    cfg = tr.config
    if cfg.fault:
        inject.arm(cfg.fault)
    epochs = epochs if epochs is not None else cfg.epochs
    history: List[Dict[str, float]] = []
    # the loop's live registry view (PR 17): step-time EWMA, epoch-lap
    # histogram, straggler ratio, h2d wait — the same numbers the
    # metrics rows log, but windowed/current for dashboards and the
    # roc-lint metric-adhoc contract (no hand-rolled accumulators in
    # the hot loop)
    reg = getattr(tr, "reg", None)
    if reg is None:
        reg = tr.reg = MetricsRegistry("train")
    g_step = reg.gauge("step_ewma_ms", ewma_alpha=0.2)
    g_strag = reg.gauge("straggler_ratio")
    g_h2d = reg.gauge("h2d_wait_p50_ms")
    h_epoch = reg.histogram("epoch_ms")
    t_last = time.perf_counter()
    e_last = tr.epoch
    compile_ms: Optional[float] = None
    # per-trainer flag, NOT tr.epoch > 0: a checkpoint-restored trainer
    # in a fresh process has epoch > 0 but still compiles on step one
    compiled = getattr(tr, "_loop_compiled", False)
    try:
        with trace(cfg.profile_dir):
            for _ in range(epochs):
                epoch = tr.epoch
                inject.note_epoch(epoch)
                lr = decayed_lr(cfg.learning_rate, jnp.asarray(epoch),
                                cfg.decay_rate, cfg.decay_steps)
                tr.key, step_key = jax.random.split(tr.key)
                do_step(step_key, lr)
                if not compiled:
                    # barrier the compile step out of the steady laps;
                    # the heartbeat turns the historical blank
                    # "claiming backend" hang into dated stall events —
                    # and, with ROC_TPU_STALL_TIMEOUT_S armed, into a
                    # StallFailure the recovery loop can restart
                    with Heartbeat("first_compile"):
                        inject.maybe_stall()
                        tr.sync()
                    now = time.perf_counter()
                    compile_ms = (now - t_last) * 1e3
                    # timer laps are the timeline span buffer
                    # (flushed per eval), not a quantile store; the
                    # registry histogram records the same lap below
                    # roc-lint: ok=metric-adhoc
                    tr.timer.laps_ms.append(compile_ms)
                    tr.timer.note_span("compile", compile_ms)
                    # clock-sync handshake, piggybacked on the barrier
                    # just crossed: every SPMD process passes the first
                    # step's collective within one step of each other,
                    # so the merger (obs/timeline.py) aligns the
                    # per-process monotonic clocks on this event's
                    # (wall, mono) pair — N per-process JSONL streams
                    # become one time axis
                    emit("timeline",
                         f"clock_sync: first-step barrier crossed "
                         f"(epoch {epoch})", console=False,
                         kind="clock_sync", epoch=epoch,
                         compile_ms=round(compile_ms, 1))
                    t_last, e_last = now, tr.epoch + 1
                    compiled = tr._loop_compiled = True
                if epoch % cfg.eval_every == cfg.eval_every - 1:
                    tr.sync()
                    now = time.perf_counter()
                    mono_now = time.monotonic()
                    m = do_eval()
                    t_eval_end = time.perf_counter()
                    m["epoch"] = epoch
                    span = tr.epoch + 1 - e_last
                    if span <= 0:
                        # no steady steps since the compile barrier
                        # (only possible on the first eval with
                        # eval_every == 1): the compile lap is the only
                        # honest number we have
                        m["epoch_ms"] = compile_ms
                    else:
                        m["epoch_ms"] = (now - t_last) * 1e3 / span
                        # span buffer, see the compile lap above
                        # roc-lint: ok=metric-adhoc
                        tr.timer.laps_ms.append(m["epoch_ms"])
                        tr.timer.spans_ms.setdefault(
                            "train", []).append(m["epoch_ms"])
                        # timeline lane: the whole steady burst as ONE
                        # span (per-epoch steps dispatch async and
                        # have no individual host-visible boundaries)
                        burst_ms = (now - t_last) * 1e3
                        tr.timer.timeline.append(
                            ("train", mono_now - burst_ms / 1e3,
                             burst_ms))
                    m["eval_ms"] = (t_eval_end - now) * 1e3
                    tr.timer.note_span("eval", m["eval_ms"])
                    if compile_ms is not None:
                        m["compile_ms"] = compile_ms
                        compile_ms = None
                    if span > 0:
                        # throughput from honest steady laps only
                        m.update(throughput_fields(tr, m["epoch_ms"]))
                    # streamed-tier pipeline metrics (overlap_frac,
                    # h2d_wait p50) accumulated over the burst
                    pipe = getattr(tr, "pipeline_fields", None)
                    if pipe is not None:
                        m.update(pipe() or {})
                    # per-epoch straggler attribution (distributed
                    # trainers): which shard the cost model predicts
                    # slowest for the measured lap, by how much — the
                    # SAME record maybe_rebalance's ridge observation
                    # consumes, now on every eval'd record and in the
                    # merged timeline
                    sf = getattr(tr, "straggler_fields", None)
                    if sf is not None:
                        m.update(sf(m) or {})
                    # registry recording + the row's EWMA field: only
                    # steady laps feed the EWMA (the compile lap would
                    # drag it for ~1/alpha evals)
                    if span > 0 and m.get("epoch_ms"):
                        h_epoch.record(m["epoch_ms"])
                        g_step.set(m["epoch_ms"])
                        ew = g_step.ewma
                        if ew is not None:
                            m["step_ewma_ms"] = round(ew, 2)
                    if m.get("straggler_ratio") is not None:
                        g_strag.set(m["straggler_ratio"])
                    if m.get("h2d_wait_p50_ms") is not None:
                        g_h2d.set(m["h2d_wait_p50_ms"])
                    t_last, e_last = t_eval_end, tr.epoch + 1
                    history.append(m)
                    tr.metrics_log.log(m)
                    # flush span laps for the timeline merger: one
                    # compact event per eval instead of one per span
                    tl = tr.timer.take_timeline()
                    if tl:
                        emit("timeline",
                             f"spans: {len(tl)} laps to epoch {epoch}",
                             console=False, kind="spans", epoch=epoch,
                             spans=[[n, round(t0, 6), round(ms, 3)]
                                    for n, t0, ms in tl])
                    # epoch-boundary load rebalancing (distributed
                    # trainers with config.rebalance): feed the
                    # measured lap to the partition cost model and
                    # repartition when the predicted max-shard gain
                    # clears the hysteresis threshold.  After a
                    # shape-changing repartition the trainer resets
                    # _loop_compiled so the recompile lap is barriered
                    # out of the steady timing like the first one.
                    rb = getattr(tr, "maybe_rebalance", None)
                    if rb is not None:
                        rb(m)
                        compiled = getattr(tr, "_loop_compiled",
                                           compiled)
                    emit("epoch",
                         f"epoch {epoch}: {m['epoch_ms']:.1f} ms/epoch "
                         f"eval {m['eval_ms']:.1f} ms",
                         console=False, **m)
                    if cfg.verbose:
                        print(format_metrics(epoch, m))
                tr.epoch += 1
                # epoch-boundary fault sites (nan_grads / sigkill /
                # sigterm drills) and the preemption grace check: the
                # in-flight step has been dispatched, so a graceful
                # stop here "finishes the epoch step" by construction
                inject.epoch_hooks(tr, epoch)
                preempt.raise_if_preempted(epoch)
    finally:
        # bound fds across many trainers — on exceptions too; the log
        # lazily reopens in append mode if train() is called again
        tr.metrics_log.close()
        tl = tr.timer.take_timeline()
        if tl:
            # span laps accumulated since the last eval flush (a run
            # dying between evals must not take them along)
            emit("timeline", f"spans: {len(tl)} laps (final)",
                 console=False, kind="spans",
                 spans=[[n, round(t0, 6), round(ms, 3)]
                        for n, t0, ms in tl])
        if tr.timer.spans_ms:
            emit("epoch", "phase spans "
                 + " ".join(f"{k}:n={v['n']},p50={v['p50_ms']:.1f}ms"
                            for k, v in
                            tr.timer.span_summary().items()),
                 console=False, spans=tr.timer.span_summary(),
                 laps=tr.timer.summary())
    return history


def throughput_fields(tr, epoch_ms: Optional[float]) -> Dict[str, float]:
    """edges/sec and MFU-style utilization for one steady epoch lap.
    FLOPs come from the compile observer's ``cost_analysis()`` capture
    (per-device under SPMD — matching the per-chip peak the MFU ratio
    divides by); missing introspection just drops the fields."""
    out: Dict[str, float] = {}
    if not epoch_ms or epoch_ms <= 0:
        return out
    s = epoch_ms / 1e3
    edges = getattr(tr, "_obs_edges", None)
    if edges:
        out["edges_per_s"] = round(edges / s, 1)
    cost = getattr(getattr(tr, "_train_step", None), "cost", None)
    if not cost:
        # features='host' streaming never calls _train_step — the
        # observed step there is the device-resident tail
        cost = getattr(getattr(tr, "_tail_grad", None), "cost", None)
    flops = (cost or {}).get("flops")
    if flops:
        out["tflops_per_s"] = round(flops / s / 1e12, 4)
        from ..obs.compile_watch import peak_flops_per_s
        peak = peak_flops_per_s()
        if peak:
            out["mfu"] = round(flops / s / peak, 4)
    return out


def format_metrics(epoch: int, m: Dict[str, float]) -> str:
    """The reference's infer-mode print line (``softmax_kernel.cu:146``)."""
    return ("[INFER][%d] train_loss: %.4f  train_accuracy: %.2f%%(%d/%d)  "
            "val_accuracy: %.2f%%(%d/%d)  test_accuracy: %.2f%%(%d/%d)"
            % (epoch, m["train_loss"],
               m["train_acc"] * 100.0, m["train_correct"], m["train_cnt"],
               m["val_acc"] * 100.0, m["val_correct"], m["val_cnt"],
               m["test_acc"] * 100.0, m["test_correct"], m["test_cnt"]))

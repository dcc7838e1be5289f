"""Distributed full-graph training over a 1-D device mesh.

TPU-native replacement for the reference's entire distribution stack
(SURVEY §2 #20-22 and §2's parallelism facets):

- **GnnMapper** (``gnn_mapper.cc:120-151``: partitions → GPUs round-robin,
  FB/ZC memory placement) → a ``jax.sharding.Mesh`` over one ``'parts'``
  axis with ``NamedSharding``s: partition p lives on device p, period.
- **Graph partition parallelism** (``gnn.cc:471-530``: vertex-range index
  launches) → ``shard_map`` over stacked per-part arrays; every op in the
  step function runs SPMD on its local partition.
- **Halo exchange** (whole-region feature requirement,
  ``scattergather.cc:70-72``; the dead explicit ``ncclAllGather`` path,
  ``gnn_kernel.cu:65-78``) → ``jax.lax.all_gather`` over ICI before each
  aggregation, in *padded part order* (edge sources are pre-remapped to
  padded coordinates at partition time).
- **Gradient reduction** (per-partition weight-grad replicas summed on one
  GPU, ``optimizer_kernel.cu:88-94``) → ``jax.lax.psum`` of local grads
  over the mesh — numerically the same sum, but bandwidth-optimal on ICI
  and with no replica memory.
- **Metrics reduction** (on-GPU atomics, ``softmax_kernel.cu:41-79``) →
  ``psum`` of the PerfMetrics sums.

Weights and optimizer state are replicated (the reference reads weights
whole in every task, ``linear.cc:95-99``); activations/labels/masks are
sharded on the node axis.  Multi-host DCN works through the same mesh via
``jax.distributed.initialize`` + ``jax.make_mesh`` over all processes'
devices.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace as dc_replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.ell import ell_from_padded_parts
from ..core.graph import Dataset, MASK_NONE
from ..core.partition import PartitionedGraph, partition_graph
from ..models.builder import GraphContext, Model
from ..obs.events import emit, flush_spans, span
from ..obs.scopes import ALLREDUCE_SCOPE, LOSS_SCOPE, OPT_SCOPE
from ..ops.loss import masked_softmax_cross_entropy, perf_metrics, summarize_metrics
from ..train.optimizer import AdamConfig, adam_init, adam_update
from ..train.trainer import (TrainConfig, batch_norm_plan, cast_compute,
                             compute_dtype_of, resolve_symmetric,
                             split_state, upload)


# THE names of the mesh axes — defined in parallel/__init__ (the
# cycle-free home ring.py / multihost.py / models/builder.py can also
# import) and re-exported here because every collective in the step
# bodies below reduces/gathers/permutes over PARTS_AXIS and the SPMD
# collective verifier (analysis/collective_lint.py) checks the traced
# eqns' axis names against the mesh built here.  MODEL_AXIS never
# appears in a step-body collective: on a 2-D mesh it is a GSPMD
# ``auto`` axis — the partitioner propagates the model sharding of
# params/opt state through the unchanged 1-D step programs.
from . import MODEL_AXIS, PARTS_AXIS, model_shard_spec


def _shard_map(f, mesh: Mesh, in_specs, out_specs,
               axis_names: frozenset = frozenset()):
    """``jax.shard_map`` with replica checking off — the step functions
    psum explicitly.  ``axis_names`` names the MANUAL mesh axes (empty
    = all of them); the rest are left to GSPMD.  The 2-D mesh's steps
    pass ``{PARTS_AXIS}``: the body stays a 1-D parts program while the
    partitioner threads the MODEL_AXIS sharding through it."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names,
                         check_vma=False)


def _is_float16(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize == 2


def _psum_wide(x):
    """``lax.psum`` over PARTS_AXIS with 16-bit floats reduced in fp32
    — see :meth:`DistributedTrainer._psum_parts`."""
    if _is_float16(x):
        return lax.psum(x.astype(jnp.float32), PARTS_AXIS).astype(x.dtype)
    return lax.psum(x, PARTS_AXIS)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_by_psum(x, pid, num_parts: int):
    """The halo gather of the partial-auto (2-D mesh) steps: local
    block ``x`` of partition ``pid`` -> all ``num_parts`` blocks,
    concatenated, as a psum of disjointly-placed blocks
    (:meth:`DistributedTrainer._local_gctx` says why not all_gather).

    16-bit floats travel as their uint16 bit patterns: the blocks are
    disjoint, so the integer sum of each pattern with zeros is exact,
    the bytes moved are unchanged, and the bf16 all-reduce that aborts
    XLA:CPU under a partial-auto axis (_psum_parts) is never built.
    The bitcast has no derivative, hence the explicit vjp: reduce the
    cotangent over parts and keep this partition's block."""
    if num_parts == 1:
        return x            # single part: gather is identity
    bits = _is_float16(x)
    xb = lax.bitcast_convert_type(x, jnp.uint16) if bits else x
    buf = jnp.zeros((num_parts,) + xb.shape, xb.dtype)
    buf = lax.psum(lax.dynamic_update_index_in_dim(buf, xb, pid, 0),
                   PARTS_AXIS)
    if bits:
        buf = lax.bitcast_convert_type(buf, x.dtype)
    return buf.reshape((num_parts * x.shape[0],) + x.shape[1:])


def _gather_by_psum_fwd(x, pid, num_parts):
    return _gather_by_psum(x, pid, num_parts), pid


def _gather_by_psum_bwd(num_parts, pid, ct):
    if num_parts == 1:
        return ct, None
    ct = _psum_wide(ct.reshape((num_parts, -1) + ct.shape[1:]))
    return lax.dynamic_index_in_dim(ct, pid, 0, keepdims=False), None


_gather_by_psum.defvjp(_gather_by_psum_fwd, _gather_by_psum_bwd)


def make_mesh(num_parts: Optional[int] = None,
              devices: Optional[List] = None,
              model: int = 1) -> Mesh:
    """Device mesh over graph partitions.  ``model=1`` (default) is
    the 1-D parts mesh — one partition per device, the reference sets
    numParts = numMachines * numGPUs the same way (``gnn.cc:62,754``);
    ``num_parts=None`` uses every device.  ``model > 1`` builds the
    ``(parts, model)`` 2-D mesh: ``num_parts * model`` devices
    reshaped parts-major, so the model replicas of one partition are
    ICI neighbors (``num_parts=None`` then uses
    ``len(devices) // model`` partitions).

    ``jax.devices()`` orders devices process-major, so consecutive
    partitions land on the same host — ring-halo hops cross DCN once
    per host (parallel/multihost.py relies on this layout)."""
    if devices is None:
        devices = jax.devices()
    model = int(model)
    if num_parts is None:
        num_parts = len(devices) // model if model > 1 else len(devices)
    n = num_parts * model
    assert len(devices) >= n, (
        f"need {n} devices ({num_parts}x{model}), have {len(devices)}")
    if model == 1:
        return Mesh(np.asarray(devices[:num_parts]), (PARTS_AXIS,))
    return Mesh(np.asarray(devices[:n]).reshape(num_parts, model),
                (PARTS_AXIS, MODEL_AXIS))


def remap_col_to_padded(plan, col: np.ndarray) -> np.ndarray:
    """Remap one partition's col array from global vertex ids to *padded
    row coordinates* (the row layout of the all-gathered feature matrix):
    global id g living in part p maps to
    ``p * part_nodes + (g - node_offset[p])``; the dummy source maps to
    ``num_parts * part_nodes`` (the appended zero row)."""
    offsets = np.asarray([l for l, _ in plan.bounds] + [plan.num_nodes],
                         dtype=np.int64)
    col = np.asarray(col, dtype=np.int64)
    dummy = plan.num_parts * plan.part_nodes
    out = np.full(col.shape, dummy, dtype=np.int64)
    real = col < plan.num_nodes
    g = col[real]
    p = np.searchsorted(offsets[1:plan.num_parts + 1], g, side="right")
    out[real] = p * plan.part_nodes + (g - offsets[p])
    assert (out <= dummy).all() and (out >= 0).all()
    return out.astype(np.int32)


def remap_to_padded(pg: PartitionedGraph) -> np.ndarray:
    """All-parts form of :func:`remap_col_to_padded` ([P, E_p] in/out)."""
    return remap_col_to_padded(pg, pg.part_col_idx)


def pad_nodes(arr: np.ndarray, pg: PartitionedGraph,
              fill: float = 0) -> np.ndarray:
    """Scatter a global per-node array [V, ...] into the stacked padded
    layout [P, part_nodes, ...]; padding rows get ``fill``."""
    shape = (pg.num_parts, pg.part_nodes) + arr.shape[1:]
    out = np.full(shape, fill, dtype=arr.dtype)
    for p in range(pg.num_parts):
        l, r = pg.bounds[p]
        if r < l:
            continue
        out[p, :r - l + 1] = arr[l:r + 1]
    return out


def unpad_nodes(arr: np.ndarray, pg: PartitionedGraph) -> np.ndarray:
    """Inverse of pad_nodes: [P, part_nodes, ...] -> [V, ...]."""
    parts = []
    for p in range(pg.num_parts):
        l, r = pg.bounds[p]
        if r >= l:
            parts.append(arr[p, :r - l + 1])
    return np.concatenate(parts, axis=0)


@dataclass
class ShardedData:
    """Device-resident sharded training data (leading axis = parts)."""
    feats: jax.Array       # [P, part_nodes, F]   P('parts')
    labels: jax.Array      # [P, part_nodes]      P('parts')
    mask: jax.Array        # [P, part_nodes]      P('parts')
    edge_src: jax.Array    # [P, part_edges]      P('parts'), padded coords
    edge_dst: jax.Array    # [P, part_edges]      P('parts'), local rows
    in_degree: jax.Array   # [P, part_nodes]      P('parts')
    ell_idx: Tuple[jax.Array, ...] = ()   # per bucket [P, rows_b, width_b]
    ell_row_pos: jax.Array = None         # [P, part_nodes]
    ell_row_id: Tuple[jax.Array, ...] = ()  # per bucket [P, rows_b]
    ring_idx: Tuple[jax.Array, ...] = ()  # (src, dst) [P, S, pair_edges]
    # sectioned layout (aggr_impl == "sectioned"): per section
    # [P, n_chunks_s, seg_rows, 8] / [P, n_chunks_s, seg_rows], plus
    # the static (start, size, win_rows, bands) metadata
    # (SectionedEll.meta).
    # For aggr_impl == "attn_flat8" / "flat_sum" the same slots carry
    # the SINGLE-section uniform width-8 tables (ids in gathered
    # coordinates, dummy == P*part_nodes; the step body routes them to
    # GraphContext flat8_idx/flat8_dst) with no sect_meta; flat_sum's
    # static window height rides flat_win (-> GraphContext.flat8_win),
    # its tile bands flat_bands (-> flat8_bands)
    sect_idx: Tuple[jax.Array, ...] = ()
    sect_sub_dst: Tuple[jax.Array, ...] = ()
    sect_meta: Tuple[Tuple[int, ...], ...] = ()
    flat_win: int = 0
    flat_bands: Tuple[Tuple[int, int], ...] = ()
    # block-dense MXU layout (aggr_impl == "bdense"): per-partition
    # dense [128,128] tiles over (local dst rows x gathered source
    # coords), padded to a uniform block count; () or
    # (a [P,nblk,128,128] u8, src_blk [P,nblk], dst_blk [P,nblk]).
    # The residual scattered edges ride the sect_* tables above.
    bd_tabs: Tuple[jax.Array, ...] = ()
    bd_vpad: int = 0        # dst tile space (covers part_nodes)
    bd_src_vpad: int = 0    # src tile space (covers gathered rows)
    bd_occupancy: Tuple[dict, ...] = ()   # per-part plan stats
    # the pad_plan_groups alignment the tables were built for: the
    # kernel's ``group`` MUST match it (the trainer validates injected
    # data — a mismatched group would reduce across dst-tile
    # boundaries and mis-aggregate with no shape error)
    bd_group: int = 1
    # padded slots / real edges of the ring tables (halo='ring' only);
    # surfaced so trainer setup can echo the SPMD-uniformity cost
    ring_padding_ratio: Optional[float] = None
    # fused-normalization weight tables (aggr_fuse, shapes mirror the
    # index tables they weight): per-bucket ell weights, per-section
    # sectioned weights, () or ([P, S, pair_edges],) ring weights,
    # () or (d_dst [P, vpad], d_src [P, src_vpad]) bdense tile scales.
    # Empty = the step derives d from in_degree and scales in-op.
    ell_w: Tuple[jax.Array, ...] = ()
    sect_w: Tuple[jax.Array, ...] = ()
    ring_w: Tuple[jax.Array, ...] = ()
    bd_scale: Tuple[jax.Array, ...] = ()
    # () or ([P] int32,): each partition's count of real rows (they
    # come first; the rest is padding), for the ops that reduce over
    # the vertex axis — attached by the trainer for a model that has
    # one (DistributedTrainer._with_real_rows); it rides the steps'
    # ``fuse_tabs`` slot, so every other model's programs are
    # unchanged
    real_rows: Tuple[jax.Array, ...] = ()


def _sectioned_tables(ptrs: np.ndarray, cols: np.ndarray,
                      pg: PartitionedGraph, src_rows: int,
                      section_rows: Optional[int], sect_sub_w: int,
                      sect_u16: bool, put,
                      fuse_d: Optional[Tuple[np.ndarray,
                                             np.ndarray]] = None):
    """Build + upload the stacked per-part sectioned tables — shared
    by the 'sectioned' branch (whole CSR) and the 'bdense' branch
    (residual CSR), so tuning knobs apply to both in one place.
    ``fuse_d`` = (d_dst [P, part_nodes], d_src [gathered_rows]) also
    bakes + uploads the fused-normalization weight tables.
    Returns (sect_idx, sect_sub_dst, sect_meta, sect_w)."""
    from ..core.ell import (default_section_rows,
                            sectioned_from_padded_parts)
    if section_rows is None:
        section_rows = default_section_rows(sect_u16)
    with span("setup.tables", table="sectioned") as s:
        sect = sectioned_from_padded_parts(
            ptrs, cols, pg.real_nodes, pg.part_nodes, src_rows=src_rows,
            section_rows=section_rows, sub_w=sect_sub_w)
        if sect_u16:
            sect = sect.with_idx_dtype(np.uint16)
        s["sub_rows"] = sum(a.size for a in sect.sub_dst)
    sect_w = ()
    if fuse_d is not None:
        with span("setup.tables", table="sect_w"):
            sect_w = sect.weight_tables(fuse_d[0], fuse_d[1])
        sect_w = tuple(put(w) for w in sect_w)
    return (tuple(put(a) for a in sect.idx),
            tuple(put(a) for a in sect.sub_dst),
            sect.meta,
            sect_w)


def shard_dataset(dataset: Dataset, pg: PartitionedGraph,
                  mesh: Mesh, dtype=jnp.float32,
                  aggr_impl: str = "segment",
                  halo: str = "gather",
                  put=None, section_rows: Optional[int] = None,
                  sect_sub_w: int = 8, sect_u16: bool = False,
                  bdense_min_fill: int = 64,
                  bdense_a_budget: Optional[int] = 2 << 30,
                  bdense_group: int = 1,
                  aggr_fuse: bool = False
                  ) -> ShardedData:
    """Build + upload the stacked per-part arrays.  ``put`` overrides
    the upload (default: replicated-process ``device_put`` with the
    parts sharding); parallel/multihost.py passes a local-shards-only
    uploader for multi-host runs.  ``sect_sub_w``/``sect_u16`` tune the
    sectioned layout exactly like the single-device path
    (train/trainer.py build_graph_context) — user-selected config is
    never silently dropped.

    ``aggr_fuse=True`` bakes the symmetric ``D^-1/2`` scales into the
    tables (fused-aggregation weight tables / bdense tile scales) for
    models rewritten by ``Model.fuse_norm_aggregate``; without them
    the fused step still runs correctly via in-op scaling."""
    sh = NamedSharding(mesh, P(PARTS_AXIS))
    if put is None:
        put = lambda x: jax.device_put(x, sh)
    hand_over = put

    def put(x, what="tables"):
        # every hand-over of this build under a ``setup.upload`` span
        return upload(x, what, put=hand_over)

    ell_idx = ()
    ell_row_pos = put(np.zeros((pg.num_parts, 1), dtype=np.int32))
    ell_row_id = ()
    ring_idx = ()
    sect_idx = ()
    sect_sub_dst = ()
    sect_meta = ()
    flat_win = 0
    flat_bands = ()
    bd_tabs = ()
    bd_vpad = 0
    bd_src_vpad = 0
    bd_occupancy = ()
    ring_padding_ratio = None
    ell_w = ()
    sect_w = ()
    ring_w = ()
    bd_scale = ()
    fuse_d = None
    if aggr_fuse:
        # d in both coordinate systems the tables index with: local
        # padded rows per part (padding rows have degree 0 -> 0) and
        # the flattened gathered layout
        from ..ops.norm import inv_sqrt_degree_np
        d_parts = inv_sqrt_degree_np(pg.part_in_degree)
        fuse_d = (d_parts, d_parts.reshape(-1))
    if halo == "ring":
        # ring tables fully describe the aggregation — skip the O(E)
        # per-edge array construction entirely and upload stubs
        from .ring import build_ring_tables, ring_weight_tables
        with span("setup.tables", table="ring"):
            rt = build_ring_tables(pg)
        ring_idx = (put(rt.src), put(rt.dst))
        if aggr_fuse:
            from ..ops.norm import inv_sqrt_degree_np as _inv
            with span("setup.tables", table="ring_w"):
                ring_w = ring_weight_tables(
                    pg, rt, _inv(dataset.graph.in_degree))
            ring_w = (put(ring_w),)
        ring_padding_ratio = rt.padding_ratio
        col_padded = np.zeros((pg.num_parts, 1), dtype=np.int32)
        edge_dst = np.zeros((pg.num_parts, 1), dtype=np.int32)
    else:
        with span("setup.tables", table="edge_list"):
            col_padded = remap_to_padded(pg)
            if aggr_impl != "segment":
                # table-driven paths never read the flat edge arrays —
                # upload stubs instead of two [P, E_p] tensors
                edge_dst = np.zeros((pg.num_parts, 1), dtype=np.int32)
            else:
                edge_dst = np.stack([
                    np.repeat(np.arange(pg.part_nodes, dtype=np.int32),
                              np.diff(pg.part_row_ptr[p]))
                    for p in range(pg.num_parts)])
        if aggr_impl == "ell":
            with span("setup.tables", table="ell") as s:
                table = ell_from_padded_parts(
                    pg.part_row_ptr, col_padded, pg.real_nodes,
                    pg.part_nodes, dummy=pg.num_parts * pg.part_nodes)
                s["slots"] = sum(a.size for a in table.idx)
            ell_idx = tuple(put(a) for a in table.idx)
            ell_row_pos = put(table.row_pos)
            ell_row_id = tuple(put(a) for a in table.row_id)
            if aggr_fuse:
                from ..core.ell import ell_weight_tables
                with span("setup.tables", table="ell_w"):
                    ell_w = ell_weight_tables(table, fuse_d[0], fuse_d[1])
                ell_w = tuple(put(w) for w in ell_w)
        elif aggr_impl == "sectioned":
            sect_idx, sect_sub_dst, sect_meta, sect_w = \
                _sectioned_tables(
                    pg.part_row_ptr, col_padded, pg,
                    src_rows=pg.num_parts * pg.part_nodes,
                    section_rows=section_rows, sect_sub_w=sect_sub_w,
                    sect_u16=sect_u16, put=put, fuse_d=fuse_d)
        elif aggr_impl == "bdense":
            # per-partition block-dense plans over the RECTANGULAR
            # tile space (local dst rows x gathered source coords —
            # ops/blockdense.py plan_blocks num_cols).  Stacked to a
            # uniform block count: short partitions pad with zero-A
            # tiles scattered into the dummy output tile, so every
            # device runs the same program (SPMD uniformity, exactly
            # the sectioned tables' padding-chunk scheme).
            from ..core.ell import clean_part_ptr
            from ..ops.blockdense import (BLOCK, U4_MAX, pack_a_u4,
                                          plan_blocks)
            src_rows = pg.num_parts * pg.part_nodes
            ptrs = [clean_part_ptr(pg.part_row_ptr[p],
                                   pg.real_nodes[p], pg.part_nodes)
                    for p in range(pg.num_parts)]

            def _mk(budget):
                # group>1 plans arrive per-part group-aligned, so
                # the stacked tail padding below extends in WHOLE
                # dummy-dst groups (nb and nblk_max multiples)
                return [plan_blocks(
                    ptrs[p], col_padded[p][:int(ptrs[p][-1])],
                    pg.part_nodes, min_fill=bdense_min_fill,
                    a_budget_bytes=budget,
                    num_cols=src_rows, group=bdense_group)
                    for p in range(pg.num_parts)]

            # same 2x-budget-then-pack policy as plan_blocks_packed,
            # decided ACROSS parts: the stacked table needs one
            # uniform trailing width, so pack all parts or none
            # (pack_a_u4 packs empty parts too).  The unpackable AND
            # over-budget case re-runs the census — accepted: it
            # needs multi-edge hubs past 4 bits plus a saturated
            # budget, and the native census is seconds even at
            # Reddit scale
            with span("setup.tables", table="bdense"):
                plans = _mk(bdense_a_budget * 2
                            if bdense_a_budget is not None else None)
                packable = all(pl.n_blocks == 0
                               or int(pl.a_blocks.max()) <= U4_MAX
                               for pl in plans)
                if packable:
                    plans = [pack_a_u4(pl) for pl in plans]
                elif bdense_a_budget is not None and any(
                        pl.a_blocks.nbytes > bdense_a_budget
                        for pl in plans):
                    plans = _mk(bdense_a_budget)
            bd_occupancy = tuple(pl.occupancy() for pl in plans)
            nblk_max = max(pl.n_blocks for pl in plans)
            if nblk_max:
                bd_vpad = plans[0].vpad
                bd_src_vpad = plans[0].src_vpad
                n_dst_tiles = bd_vpad // BLOCK
                a_w = BLOCK // 2 if packable else BLOCK
                with span("setup.tables", table="bdense_stack"):
                    a = np.zeros((pg.num_parts, nblk_max, BLOCK, a_w),
                                 dtype=np.uint8)
                    sblk = np.zeros((pg.num_parts, nblk_max),
                                    dtype=np.int32)
                    # padding blocks target the dummy output tile
                    # (index n_dst_tiles) — zero A keeps them
                    # numerically inert, the dummy dst keeps even
                    # rounding noise off real rows
                    dblk = np.full((pg.num_parts, nblk_max), n_dst_tiles,
                                   dtype=np.int32)
                    for p, pl in enumerate(plans):
                        nb = pl.n_blocks
                        a[p, :nb] = pl.a_blocks
                        sblk[p, :nb] = pl.src_blk
                        dblk[p, :nb] = pl.dst_blk
                bd_tabs = (put(a), put(sblk), put(dblk))
                if aggr_fuse:
                    # in-register tile scales (ops/blockdense.py):
                    # dst covers local padded rows, src the gathered
                    # layout (identical on every part — replicated
                    # rows keep the stacked-upload convention)
                    dd = np.zeros((pg.num_parts, bd_vpad), np.float32)
                    dd[:, :pg.part_nodes] = fuse_d[0]
                    ds1 = np.zeros(bd_src_vpad, np.float32)
                    ds1[:src_rows] = fuse_d[1]
                    ds = np.broadcast_to(
                        ds1, (pg.num_parts, bd_src_vpad)).copy()
                    bd_scale = (put(dd), put(ds))
            # residual scattered edges -> the stacked sectioned tables
            # (every edge, when no tile qualifies anywhere)
            with span("setup.tables", table="bdense_residual"):
                e_res = max(max(pl.res_col.shape[0] for pl in plans), 1)
                res_ptrs = np.stack([pl.res_row_ptr for pl in plans])
                res_cols = np.zeros((pg.num_parts, e_res),
                                    dtype=np.int32)
                for p, pl in enumerate(plans):
                    res_cols[p, :pl.res_col.shape[0]] = pl.res_col
            sect_idx, sect_sub_dst, sect_meta, sect_w = \
                _sectioned_tables(
                    res_ptrs, res_cols, pg, src_rows=src_rows,
                    section_rows=section_rows, sect_sub_w=sect_sub_w,
                    sect_u16=sect_u16, put=put, fuse_d=fuse_d)
        elif aggr_impl in ("attn_flat8", "flat_sum"):
            # the uniform flat layout, sharded: per-partition SINGLE-
            # section tables over gathered coordinates (one uniform
            # scan shape per device — the same compile-size fix as the
            # single-chip path, train/trainer.py make_graph_context).
            # The flat tables ride the sect_* slots (ShardedData
            # docstring); the step body routes them to the
            # GraphContext flat8 fields.  FLAT_SEG_ROWS bounds the
            # per-chunk transient like there.  For the fused flat_sum
            # path the baked D^-1/2 weight tables ride the sect_w slot
            # the same way.
            from ..core.ell import flat_sum_from_padded_parts
            src_rows = pg.num_parts * pg.part_nodes
            with span("setup.tables", table="flat_sum") as s:
                sect = flat_sum_from_padded_parts(
                    pg.part_row_ptr, col_padded, pg.real_nodes,
                    pg.part_nodes, src_rows=src_rows)
                s["sub_rows"] = sum(a.size for a in sect.sub_dst)
            sect_idx = tuple(put(a) for a in sect.idx)
            sect_sub_dst = tuple(put(a) for a in sect.sub_dst)
            if aggr_impl == "flat_sum":
                flat_win = sect.win_rows[0]
                flat_bands = sect.bands[0]
                if fuse_d is not None:
                    with span("setup.tables", table="flat_sum_w"):
                        sect_w = sect.weight_tables(fuse_d[0], fuse_d[1])
                    sect_w = tuple(put(w) for w in sect_w)
        if aggr_impl != "segment":
            col_padded = np.zeros((pg.num_parts, 1), dtype=np.int32)
    with span("setup.tables", table="padded_rows"):
        feats = pad_nodes(dataset.features, pg).astype(dtype)
        labels = pad_nodes(dataset.labels, pg)
        mask = pad_nodes(dataset.mask, pg, fill=MASK_NONE)
    return ShardedData(
        feats=put(feats, "features"),
        labels=put(labels, "labels"),
        mask=put(mask, "mask"),
        edge_src=put(col_padded),
        edge_dst=put(edge_dst),
        in_degree=put(pg.part_in_degree),
        ell_idx=ell_idx,
        ell_row_pos=ell_row_pos,
        ell_row_id=ell_row_id,
        ring_idx=ring_idx,
        sect_idx=sect_idx,
        sect_sub_dst=sect_sub_dst,
        sect_meta=sect_meta,
        flat_win=flat_win,
        flat_bands=flat_bands,
        bd_tabs=bd_tabs,
        bd_vpad=bd_vpad,
        bd_src_vpad=bd_src_vpad,
        bd_occupancy=bd_occupancy,
        bd_group=bdense_group if bd_tabs else 1,
        ring_padding_ratio=ring_padding_ratio,
        ell_w=ell_w,
        sect_w=sect_w,
        ring_w=ring_w,
        bd_scale=bd_scale,
    )


def put_replicated(tree, mesh: Mesh):
    """Place a host pytree across every device of ``mesh``: fully
    replicated on a 1-D parts mesh (the reference reads weights whole
    in every task, ``linear.cc:95-99``), and model-SHARDED on a 2-D
    ``(parts, model)`` mesh — each leaf whose shape carries a
    model-divisible dim (``parallel.model_shard_spec``, trailing dim
    first: the feature dim of every weight matrix / Adam moment here)
    splits it over MODEL_AXIS and stays replicated over parts;
    indivisible leaves (small biases) stay fully replicated.

    Single-process this is a plain ``device_put``; multi-process it
    assembles each global array from this process's addressable shards
    (``device_put`` cannot place onto non-addressable devices) — the
    bootstrap analog of the reference broadcasting initial weights to
    every GPU (``gnn.cc:78-91`` model build + Legion region mapping).
    """
    model = int(dict(mesh.shape).get(MODEL_AXIS, 1))

    def sharding_of(x):
        spec = model_shard_spec(np.shape(x), model)
        return NamedSharding(mesh, P(*spec) if spec else P())

    if jax.process_count() == 1:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding_of(x)), tree)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding_of(x),
                                            lambda idx: x[idx])
    return jax.tree_util.tree_map(put, tree)


class DistributedTrainer:
    """The reference epoch loop (``gnn.cc:99-111``) run SPMD over the
    partition mesh.

    ``data`` injects pre-built sharded tables — the multi-host entry
    point: each process runs ``multihost.shard_dataset_local`` (only
    its own partitions' rows) and passes the result here; the default
    is the single-controller ``shard_dataset`` build.  The caller must
    build ``data`` with the same ``aggr_impl``/``halo`` the config
    resolves to, and should pass the ``pg`` it built the data from
    (otherwise the identical O(E) partitioning runs a second time)."""

    def __init__(self, model: Model, dataset: Dataset, num_parts: int,
                 config: TrainConfig = TrainConfig(),
                 mesh: Optional[Mesh] = None,
                 data: Optional[ShardedData] = None,
                 pg=None):
        from ..train.trainer import resolve_config, resolve_partition
        # the ONE resolve pass (train/trainer.py resolve_config):
        # fuse, the shared 'auto' rule incl. the bdense structure
        # probe (global dense fraction is the right proxy — per-part
        # plans tile contiguous local row ranges of the same vertex
        # order; the gather-table bound uses the GLOBAL node count,
        # the scatter-carry bound the per-partition output rows),
        # memory autopilot with the A-budget charged, attention impl
        # (multi-chip attention at >=20M edges auto-routes to the
        # uniform flat8 layout — VERDICT r4 weak #3).  Multi-process
        # runs skip the probe — every SPMD process must resolve
        # identically.
        model, config, _ = resolve_config(
            model, dataset, config, num_parts=num_parts,
            multiprocess=jax.process_count() > 1)
        self.model = model
        if model.uses_dot_attention():
            from ..models.builder import TFATTN_PARTITION_REFUSAL
            raise NotImplementedError(TFATTN_PARTITION_REFUSAL)
        if config.features == "host":
            raise NotImplementedError(
                "features='host' streaming is single-device only; the "
                "distributed >HBM mechanism is halo='ring' (the "
                "autopilot picks it automatically for parts > 1)")
        self.config = config
        self.compute = compute_dtype_of(config)
        self.epoch = 0
        self.symmetric = resolve_symmetric(dataset, config.symmetric)
        # (parts, model) mesh knob: resolve_mesh validates the config
        # against the positional parts count (they must agree — the
        # parts axis IS the partition count); an injected mesh wins,
        # and the model width is always read back off the mesh actually
        # trained on so the sharding/step construction below cannot
        # disagree with it
        from ..train.trainer import resolve_mesh
        _, mesh_model = resolve_mesh(
            config, num_parts=num_parts,
            num_devices=len(jax.devices()) if mesh is None else None)
        self.mesh = mesh if mesh is not None else make_mesh(
            num_parts, model=mesh_model)
        self._mesh_model = int(dict(self.mesh.shape).get(MODEL_AXIS, 1))
        if pg is not None and pg.num_parts != num_parts:
            raise ValueError(f"injected pg has {pg.num_parts} parts, "
                             f"trainer was asked for {num_parts}")
        if data is not None and pg is None:
            # re-partitioning here could use different padding
            # multiples than the caller's table build — the tables
            # would silently stop corresponding to the feats sharding
            raise ValueError(
                "pass pg= alongside data= (the SAME PartitionedGraph "
                "the tables were built from)")
        # cost-model-driven partitioning (core/costmodel.py): resolve
        # the split method, hold the online ridge model, and keep the
        # dataset so maybe_rebalance can rebuild shards at epoch
        # boundaries
        from ..core.costmodel import PartitionCostModel
        self._dataset = dataset
        self._partition_method = resolve_partition(config)
        # workload flags for the φ features only this config pays:
        # the per-edge softmax column (attention models) and the
        # flat8 scan-length column (the flat layout family)
        self._phi_flags = dict(
            attn_edges=bool(self.model.uses_attention()),
            flat8=config.aggr_impl in ("attn_flat8", "flat_sum"))
        self._costmodel = PartitionCostModel(
            node_multiple=8, edge_multiple=config.chunk)
        self._rebalances = 0
        self._phi_cache = None
        if config.rebalance:
            if data is not None:
                # injected tables may have been built by a different
                # process/loader (multihost) — this trainer cannot
                # rebuild them faithfully mid-run
                raise ValueError(
                    "rebalance=True requires the trainer-owned data "
                    "build; injected data= cannot be repartitioned")
            if jax.process_count() > 1:
                raise NotImplementedError(
                    "online rebalancing is single-controller only "
                    "(every SPMD process would need to agree on the "
                    "re-split and reshard over DCN)")
        if pg is None:
            with span("setup.partition", parts=num_parts,
                      method=self._partition_method):
                pg = partition_graph(
                    dataset.graph, num_parts,
                    node_multiple=8, edge_multiple=config.chunk,
                    method=self._partition_method,
                    cost_weights=self._costmodel.search_weights(
                        **self._phi_flags))
        self.pg = pg
        self.data = self._with_real_rows(
            data if data is not None else self._build_data(self.pg))
        if config.aggr_impl == "bdense" and config.halo != "ring" \
                and data is None:
            # own build only: injected data carries no plan to report
            # (an empty bd_tabs there means the CALLER never planned,
            # not that no tile qualified)
            for p, occ in enumerate(self.data.bd_occupancy):
                emit("plan", f"bdense part {p}: {occ['n_blocks']} "
                     f"blocks, dense_frac={occ['dense_frac']}, "
                     f"mean_fill={occ['mean_fill']}",
                     console=config.verbose, part=p, **occ)
            if not self.data.bd_tabs:
                # changes the effective execution path — echoes
                # unconditionally, like the single-device fallback
                # (train/trainer.py)
                emit("plan", "bdense: no [128,128] tile reaches "
                     f"min_fill={config.bdense_min_fill} on any "
                     "partition — running the pure sectioned residual")
        if data is not None:
            # the autopilot / auto-resolution above may have settled on
            # a different halo/aggr_impl than the caller built tables
            # for — fail HERE with the mismatch, not mid-step with an
            # opaque shape error
            if config.halo == "ring" and not self.data.ring_idx:
                raise ValueError(
                    "injected data has no ring tables but the resolved "
                    "config wants halo='ring' (build it with "
                    "shard_dataset_local(..., halo='ring') or pass "
                    "memory/halo explicitly)")
            if config.halo != "ring":
                if config.aggr_impl in ("sectioned", "attn_flat8",
                                        "flat_sum", "bdense") \
                        and not self.data.sect_idx:
                    raise ValueError(
                        f"injected data has no sectioned/flat tables "
                        f"but the resolved aggr_impl is "
                        f"{config.aggr_impl!r} — build it with the "
                        f"same aggr_impl (note: attention/sum models "
                        f"at >=20M edges auto-route to the flat "
                        f"layouts)")
                if config.aggr_impl in ("sectioned", "bdense") \
                        and self.data.sect_idx \
                        and not self.data.sect_meta:
                    # flat8-built tables carry sect_idx but no
                    # sect_meta — aggregate_ell_sect would zip over
                    # () and return all-zero aggregations silently
                    raise ValueError(
                        f"injected data carries flat8-style tables "
                        f"(no section metadata) but the resolved "
                        f"aggr_impl is {config.aggr_impl!r} — build "
                        f"it with the same aggr_impl")
                if config.aggr_impl == "bdense" \
                        and self.data.bd_tabs \
                        and self.data.bd_group != config.bdense_group:
                    # a group mismatch would reduce across dst-tile
                    # boundaries (or trip the kernel's alignment
                    # check) — fail here with the cause, not mid-step
                    raise ValueError(
                        f"injected data was built with bdense_group="
                        f"{self.data.bd_group} but the config wants "
                        f"bdense_group={config.bdense_group} — build "
                        f"it with shard_dataset(..., bdense_group="
                        f"{config.bdense_group})")
                if config.aggr_impl == "bdense" \
                        and not self.data.bd_tabs \
                        and not self.data.bd_occupancy:
                    # sectioned-built data passes the two checks above
                    # (sect_idx + sect_meta both present) but would
                    # silently run residual-only; a genuine bdense
                    # build always records per-part occupancy, even
                    # when no tile qualifies and bd_tabs stays empty
                    raise ValueError(
                        "injected data carries no block-dense plan "
                        "but the resolved aggr_impl is 'bdense' — "
                        "build it with shard_dataset(..., "
                        "aggr_impl='bdense')")
                if config.aggr_impl == "bdense" \
                        and not self.data.bd_tabs:
                    # planned, but no [128,128] tile reached min_fill:
                    # the step runs the pure sectioned residual — same
                    # echo as the own-build path below
                    emit("plan", "bdense: injected plan has no dense "
                         "tiles — running the pure sectioned residual")
                if config.aggr_impl == "ell" \
                        and not self.data.ell_idx:
                    raise ValueError(
                        f"injected data has no ELL tables but the "
                        f"resolved aggr_impl is "
                        f"{config.aggr_impl!r} — build it with "
                        f"aggr_impl='ell'")
                if config.aggr_impl == "segment" and \
                        self.data.edge_dst.shape[-1] != \
                        self.pg.part_edges:
                    # table-built data carries 1-element edge stubs;
                    # the edge-list reference would silently aggregate
                    # one fake 0->0 edge per part
                    raise ValueError(
                        f"injected data carries edge stubs "
                        f"(shape {tuple(self.data.edge_dst.shape)}) "
                        f"but the resolved aggr_impl "
                        f"{config.aggr_impl!r} reads the flat edge "
                        f"arrays — build the data with the same "
                        f"aggr_impl")
        if config.halo == "ring" and self.data.ring_idx:
            # startup echo like the reference's config print
            # (gnn.cc:48-60): make the SPMD padding cost visible, and
            # say out loud that ring tables subsume the aggr impl
            ratio = self.data.ring_padding_ratio
            emit("plan", f"halo=ring: P={self.pg.num_parts} "
                 f"pair_edges={self.data.ring_idx[0].shape[2]} "
                 f"padding_ratio="
                 f"{'?' if ratio is None else format(ratio, '.2f')} "
                 f"overlap={'on' if config.ring_overlap else 'off'} "
                 f"(aggr_impl={config.aggr_impl!r} unused: ring tables "
                 f"drive the aggregation)", console=config.verbose,
                 num_parts=self.pg.num_parts,
                 pair_edges=int(self.data.ring_idx[0].shape[2]),
                 padding_ratio=ratio,
                 ring_overlap=bool(config.ring_overlap))
        with span("setup.params") as s:
            key = jax.random.PRNGKey(config.seed)
            self.key, init_key = jax.random.split(key)
            host_params = model.init_params(init_key, dtype=config.dtype)
            self.params = put_replicated(host_params, self.mesh)
            self.opt_state = put_replicated(
                adam_init(split_state(host_params,
                                      model.state_names())[0]),
                self.mesh)
            s["param_bytes"] = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        self.adam_cfg = AdamConfig(weight_decay=config.weight_decay)
        # observability: per-device modeled bytes for the compile
        # observer's modeled-vs-actual check, edges for edges/sec
        from ..train.trainer import modeled_plan
        self._obs_edges = int(dataset.graph.num_edges)
        with span("setup.resolve"):
            self._plan = modeled_plan(model, dataset, config,
                                      num_parts=num_parts)
        self._modeled_bytes = self._plan["est_bytes"]
        # dataset identity for the checkpoint config fingerprint; the
        # elastic half (num_parts + quantized plan shapes) reads
        # self.pg directly (utils/checkpoint.trainer_fingerprint)
        self._fp_dataset = {"V": int(dataset.graph.num_nodes),
                            "E": int(dataset.graph.num_edges)}
        with span("setup.steps"):
            self._build_steps()
        from ..obs.manifest import run_manifest
        with span("setup.manifest"):
            # split-quality record: per-part padded shapes + halo rows
            # + imbalance ratios, into the manifest (every run records
            # the split it actually trained on) and the costmodel
            # event stream
            self._partition_stats = self._emit_partition_stats()
            run_manifest(
                config=self.config, dataset=dataset, model=model,
                num_parts=num_parts,
                extra={"modeled_step_bytes": self._modeled_bytes,
                       "bd_occupancy": list(self.data.bd_occupancy),
                       "partition": self._partition_stats},
                agg_window={
                    **self._gctx().agg_window(
                        model._ops, tables=self.data.sect_idx,
                        edges=int(dataset.graph.num_edges),
                        compute=self.compute),
                    **self._gctx().attention_plan(
                        model._ops, ell_idx=self.data.ell_idx,
                        flat8_idx=next(iter(self.data.sect_idx), None)),
                    **self._gctx().soft_plan(model._ops, self.compute),
                    **batch_norm_plan(model._ops,
                                      dataset.graph.num_nodes),
                    "memory_plan": self._plan},
                console=config.verbose)
        from ..utils.profiling import EpochTimer, MetricsLog
        # annotate=True routes every phase span through
        # jax.profiler.TraceAnnotation so --profile-dir device
        # traces carry the same named phases as the timeline lanes
        self.timer = EpochTimer(
            annotate=bool(config.profile_dir))
        self.metrics_log = MetricsLog(config.metrics_path)
        # set-up's spans, cli.main's among them, as one batch
        flush_spans("setup")

    def _with_real_rows(self, data: ShardedData) -> ShardedData:
        """``data`` with each partition's real-row count attached —
        for a model that reduces over the vertex axis (``batch_norm``,
        the softmax aggregation's shift), whose moments must leave
        partition padding out; any other model's data is returned as
        it came, and its step programs are what they were."""
        if not any(op.kind in ("batch_norm", "soft_aggregate")
                   for op in self.model._ops):
            return data
        counts = np.asarray([max(r - l + 1, 0)
                             for l, r in self.pg.bounds], dtype=np.int32)
        return dc_replace(data, real_rows=(jax.device_put(
            counts, NamedSharding(self.mesh, P(PARTS_AXIS))),))

    def _build_data(self, pg) -> ShardedData:
        """Build + upload the sharded tables for ``pg`` with the
        trainer's resolved knobs — shared by __init__ and the
        repartitioning path (the halo/ring/sectioned/bdense tables are
        all rebuilt from the new bounds here)."""
        config = self.config
        return shard_dataset(
            self._dataset, pg, self.mesh,
            dtype=self.compute,
            aggr_impl=config.aggr_impl,
            halo=config.halo,
            sect_sub_w=config.sect_sub_w,
            sect_u16=config.sect_u16,
            bdense_min_fill=config.bdense_min_fill,
            bdense_a_budget=config.bdense_a_budget,
            bdense_group=config.bdense_group,
            aggr_fuse=self.model.num_fused_aggregates() > 0)

    def _step_auto(self) -> frozenset:
        """Mesh axes the shard_map steps leave to GSPMD: the model
        axis of a 2-D mesh (the step bodies stay 1-D parts programs —
        no in/out spec names MODEL_AXIS, and the partitioner threads
        the params' model sharding through them); empty on the 1-D
        mesh so the traced programs there are byte-identical to
        before.

        Empty for halo='ring' even on a 2-D mesh: under a partial-auto
        shard_map this jax/XLA only supports ``psum`` over the manual
        axes — ``all_gather``/``ppermute`` abort the SPMD partitioner
        (IsManualSubgroup check) and ``axis_index`` lowers to an
        unsupported PartitionId.  The gather/table paths route around
        it (a psum-based gather + the part index as a sharded
        argument, below), but the ring schedule is a ppermute loop by
        construction — so ring steps run fully manual over BOTH axes
        instead: every model replica runs the identical 1-D ring
        program and params/opt state stay model-sharded AT REST only
        (the jit in/out shardings still apply)."""
        return (frozenset({MODEL_AXIS})
                if self._mesh_model > 1 and self.config.halo != "ring"
                else frozenset())

    def _step_shardings(self):
        """Explicit per-arg jit shardings for the 2-D-mesh steps, or
        None on the 1-D mesh (where today's exact jit construction —
        and hence the rigs' program keys — must stay byte-identical).
        params/opt-state leaves pin their at-rest model sharding on
        BOTH sides of the step, which is what keeps donation legal
        under sharding (the donated input and the matching output
        must agree on layout); data/table args pin the parts split
        (a pytree-prefix sharding covers each nested table tuple);
        key/lr/metrics stay replicated."""
        if self._mesh_model <= 1:
            return None
        mesh, model = self.mesh, self._mesh_model

        def of(x):
            spec = model_shard_spec(np.shape(x), model)
            return NamedSharding(mesh, P(*spec) if spec else P())
        params_sh = jax.tree_util.tree_map(of, self.params)
        opt_sh = jax.tree_util.tree_map(of, self.opt_state)
        psh = NamedSharding(mesh, P(PARTS_AXIS))
        rep = NamedSharding(mesh, P())
        # the partial-auto steps take one extra trailing arg: the
        # parts-sharded partition-index vector (_step_auto explains
        # why axis_index cannot be used there)
        extra = (psh,) if self._step_auto() else ()
        return ((params_sh, opt_sh) + (psh,) * 14 + (rep, rep) + extra,
                (params_sh, opt_sh, rep),
                (params_sh,) + (psh,) * 14 + extra,
                (rep, psh))

    def _build_steps(self) -> None:
        """(Re)build the observed step functions.  Called at init and
        after a shape-changing repartition; a shape-preserving
        repartition keeps the existing ObservedJit objects so the
        steady-state AOT executables are reused (no recompile)."""
        from ..obs.compile_watch import ObservedJit
        config = self.config
        sharded = self._step_shardings()
        # partial-auto steps read their partition index from this
        # parts-sharded vector (one extra trailing arg) because
        # lax.axis_index is not lowerable under a GSPMD auto axis
        self._pids = None
        if self._step_auto():
            self._pids = jax.device_put(
                np.arange(self.pg.num_parts, dtype=np.int32),
                NamedSharding(self.mesh, P(PARTS_AXIS)))
        # the jax.jit calls sit lexically inside ObservedJit(jitfn=...)
        # — the sanctioned form roc-lint's bare-jit rule recognizes:
        # every step compiles through the observer
        if sharded is None:
            self._train_step = ObservedJit(
                jitfn=jax.jit(self._build_train_step(),
                              donate_argnums=(0, 1)),
                name="dist_train_step", donate_argnums=(0, 1),
                modeled_bytes=self._modeled_bytes,
                verbose=config.verbose)
        else:
            # 2-D mesh: pin the at-rest model sharding of params/opt
            # state on both sides of the step (the pjit per-arg
            # partition-spec + donation-vector pattern) so donation
            # stays legal under sharding — the PR-14
            # donation-under-sharding rule is the tripwire
            t_in, t_out, _, _ = sharded
            self._train_step = ObservedJit(
                jitfn=jax.jit(self._build_train_step(),
                              in_shardings=t_in, out_shardings=t_out,
                              donate_argnums=(0, 1)),
                name="dist_train_step", donate_argnums=(0, 1),
                modeled_bytes=self._modeled_bytes,
                verbose=config.verbose)
        # eval and predict share ONE compiled program: the eval step
        # returns (replicated metrics, SHARDED per-part logits) — the
        # logits already exist inside the step, so the extra output is
        # one [part_nodes, C] device buffer per eval, no collective,
        # and the program space loses a whole compiled program per
        # config (ISSUE 7).  evaluate() fetches only the metrics.
        if sharded is None:
            self._eval_step = ObservedJit(
                jitfn=jax.jit(self._build_eval_step()),
                name="dist_eval_step", verbose=config.verbose)
        else:
            _, _, e_in, e_out = sharded
            self._eval_step = ObservedJit(
                jitfn=jax.jit(self._build_eval_step(),
                              in_shardings=e_in, out_shardings=e_out),
                name="dist_eval_step", verbose=config.verbose)
        # multi-process predict needs the sharded logits replicated
        # before the host fetch; built lazily, never on rigs/tests
        self._predict_gather = None

    def _emit_partition_stats(self) -> dict:
        """Compute + emit the split-quality record for the CURRENT
        partition; returns the stats dict.  The O(E) feature pass is
        paid ONCE here — the φ matrix lands in ``_phi_cache`` so the
        rebalance hook never recomputes it for the same split."""
        from ..core.costmodel import (partition_static_stats,
                                      phi_matrix)
        self._phi_cache = phi_matrix(
            self.pg, bd_occupancy=self.data.bd_occupancy,
            **self._phi_flags)
        stats = partition_static_stats(
            self.pg, bd_occupancy=self.data.bd_occupancy,
            phi=self._phi_cache)
        emit("costmodel",
             f"partition={self._partition_method}: "
             f"P={stats['num_parts']} "
             f"part_nodes={stats['part_nodes']} "
             f"part_edges={stats['part_edges']} "
             f"edge imbalance (max/mean) {stats['edge_imbalance']:.2f} "
             f"node {stats['node_imbalance']:.2f}",
             console=self.config.verbose,
             method=self._partition_method, **stats)
        return stats

    # ---- online load rebalancing (core/costmodel.py) ----

    @staticmethod
    def _static_signature(pg, data: ShardedData):
        """Everything the compiled step specializes on: padded shape
        statics plus every table's (shape, dtype) and the static aux
        the GraphContext pytree carries.  Two partitions with equal
        signatures trace to the same executable, so the repartition
        path may keep the compiled step; any difference forces a
        rebuild (stale trace-time constants would otherwise
        mis-aggregate silently)."""
        def sh(x):
            if x is None:
                return None
            if isinstance(x, (tuple, list)):
                return tuple(sh(v) for v in x)
            if hasattr(x, "shape"):
                return (tuple(x.shape), str(x.dtype))
            return x
        return (pg.part_nodes, pg.part_edges, pg.num_parts,
                sh(data.feats), sh(data.labels), sh(data.mask),
                sh(data.edge_src), sh(data.edge_dst),
                sh(data.in_degree), sh(data.ell_idx),
                sh(data.ell_row_pos), sh(data.ell_row_id),
                sh(data.ring_idx), sh(data.sect_idx),
                sh(data.sect_sub_dst), sh(data.sect_meta),
                data.flat_win, data.flat_bands, sh(data.bd_tabs),
                data.bd_vpad,
                data.bd_src_vpad, data.bd_group, sh(data.ell_w),
                sh(data.sect_w), sh(data.ring_w), sh(data.bd_scale))

    def _phi(self) -> np.ndarray:
        """Cached per-partition feature matrix for the CURRENT split
        (recomputed only after a repartition — the O(E) halo pass must
        not run every eval)."""
        if self._phi_cache is None:
            from ..core.costmodel import phi_matrix
            self._phi_cache = phi_matrix(
                self.pg, bd_occupancy=self.data.bd_occupancy,
                **self._phi_flags)
        return self._phi_cache

    def straggler_fields(self, m: Dict[str, float]) -> Dict[str, float]:
        """Per-epoch straggler attribution (run_epoch_loop folds this
        into every eval'd metrics record): which shard the partition
        cost model predicts slowest for the measured lap, and by how
        much over the mean — the SAME attribution
        :meth:`maybe_rebalance`'s ridge observation consumes (under
        lockstep SPMD only the straggler's time is observable, PR-5
        cost model).  Emits a ``costmodel`` straggler event with the
        full predicted per-shard cost vector so the merged timeline
        (obs/timeline.py) can render per-epoch attribution markers."""
        t = (m.get("epoch_ms")
             if m.get("compile_ms") is None else None)
        if not t:
            # a record that folded the compile lap in would attribute
            # compile seconds to a shard — same skip rule as the
            # rebalance observation below
            return {}
        # _phi() is the init-cached matrix (_emit_partition_stats pays
        # the O(E) feature pass once per split, rebalance on or off);
        # predict is a P x n_features dot — per-eval cost is trivial
        pred = self._costmodel.predict(self._phi())
        p = int(np.argmax(pred))
        mean = float(np.mean(pred))
        ratio = round(float(pred[p]) / mean, 4) if mean > 0 else None
        out: Dict[str, float] = {"straggler_part": p,
                                 "straggler_ratio": ratio}
        emit("costmodel",
             f"straggler: epoch {m.get('epoch')} lap {t:.1f} ms -> "
             f"part {p} (predicted {ratio}x the {self.pg.num_parts}-"
             f"shard mean)", console=False, kind="straggler",
             epoch=m.get("epoch"), measured_ms=float(t),
             num_parts=self.pg.num_parts,
             predicted_cost=[round(float(c), 3) for c in pred], **out)
        return out

    def maybe_rebalance(self, m: Dict[str, float]) -> bool:
        """Epoch-boundary rebalancing hook (run_epoch_loop calls this
        after every eval record): feed the measured lap to the online
        ridge model (attributed to the predicted-slowest shard — under
        lockstep SPMD only the straggler's time is observable), search
        a new split under the refitted weights, and repartition when
        the predicted max-shard gain clears the hysteresis threshold
        (``rebalance_gain``, at most ``rebalance_max`` times).
        Returns True when a repartition happened."""
        cfg = self.config
        if not cfg.rebalance or self._rebalances >= cfg.rebalance_max:
            return False
        from ..core.costmodel import (bounds_max_cost,
                                      cost_balanced_bounds)
        # a record carrying compile_ms may have folded the compile
        # lap into epoch_ms (run_epoch_loop's span<=0 branch at
        # eval_every=1, and again after a shape-changing repartition)
        # — a multi-second compile observed as a step time would
        # inflate the straggler's fitted weights by orders of
        # magnitude, so that eval's observation is skipped
        t = (m.get("epoch_ms")
             if m.get("compile_ms") is None else None)
        if t:
            phi = self._phi()
            p_star = int(np.argmax(self._costmodel.predict(phi)))
            self._costmodel.observe(phi[p_star], float(t))
            emit("costmodel",
                 f"observe: epoch {m.get('epoch')} lap {t:.1f} ms "
                 f"attributed to part {p_star}", console=False,
                 part=p_star, epoch_ms=float(t),
                 n_obs=self._costmodel.n_obs)
        wn, we = self._costmodel.search_weights(**self._phi_flags)
        row_ptr = self._dataset.graph.row_ptr
        nm = self.pg.node_multiple
        em = self.pg.edge_multiple
        cur = bounds_max_cost(row_ptr, self.pg.bounds, wn, we, nm, em)
        new_bounds = cost_balanced_bounds(
            row_ptr, self.pg.num_parts, node_multiple=nm,
            edge_multiple=em, weights=(wn, we))
        new = bounds_max_cost(row_ptr, new_bounds, wn, we, nm, em)
        gain = 1.0 - new / cur if cur > 0 else 0.0
        same = [tuple(b) for b in new_bounds] == \
            [tuple(b) for b in self.pg.bounds]
        if same or gain <= cfg.rebalance_gain:
            emit("costmodel",
                 f"rebalance: predicted max-shard gain {gain:.1%} "
                 f"<= threshold {cfg.rebalance_gain:.0%} — keeping "
                 f"the current split", console=False,
                 gain=round(gain, 4), threshold=cfg.rebalance_gain)
            return False
        self._repartition(new_bounds, gain=gain)
        return True

    def _repartition(self, bounds, gain: Optional[float] = None
                     ) -> None:
        """Rebuild PartitionedGraph + ShardedData for ``bounds`` and
        resume.  Quantization to the plan's node/edge multiples means
        an unchanged static signature reuses the compiled step (no
        recompile — the tables are runtime arguments); a changed one
        rebuilds the observed steps and re-barriers the compile lap.
        Replicated params/opt state are untouched: full-batch training
        makes the switch numerics-preserving."""
        from ..core.partition import materialize_plan, plan_from_bounds
        g = self._dataset.graph
        old_edges = self.pg.part_edges
        plan = plan_from_bounds(
            g.row_ptr, [tuple(b) for b in bounds], self.pg.num_parts,
            node_multiple=self.pg.node_multiple,
            edge_multiple=self.pg.edge_multiple)
        pg2 = materialize_plan(g, plan)
        data2 = self._build_data(pg2)
        recompile = (self._static_signature(pg2, data2)
                     != self._static_signature(self.pg, self.data))
        self.pg = pg2
        self.data = self._with_real_rows(data2)
        self._phi_cache = None
        self._rebalances += 1
        if recompile:
            self._build_steps()
            # barrier the recompile lap out of the steady timing,
            # exactly like the first compile (run_epoch_loop)
            self._loop_compiled = False
        self._partition_stats = self._emit_partition_stats()
        emit("costmodel",
             f"repartition #{self._rebalances}: predicted max-shard "
             f"gain {'?' if gain is None else format(gain, '.1%')}, "
             f"part_edges {old_edges} -> {pg2.part_edges}, "
             + ("recompiling steps" if recompile else
                "quantized shapes unchanged — compiled step reused"),
             rebalance=self._rebalances,
             gain=None if gain is None else round(gain, 4),
             recompile=recompile, part_edges=pg2.part_edges,
             part_nodes=pg2.part_nodes)
        # the rebuild's table and upload laps, as a batch of their own
        flush_spans("repartition")

    # ---- step builders ----

    def _psum_parts(self, t):
        """``lax.psum`` over PARTS_AXIS, elided on a single-part mesh:
        a size-1 manual axis still emits a cross-partition allreduce,
        which the partial-auto partitioner rejects (1xM meshes) — and
        the sum over one part is the identity anyway.

        On a partial-auto (2-D mesh) step, 16-bit float leaves reduce
        in fp32: XLA:CPU's AllReducePromotion pass aborts the process
        on a bf16 all-reduce whose reduction region the partitioner
        rewrote ("Invalid binary instruction opcode copy", jaxlib
        0.9.0).  Only pure-bf16 gradients take this route — the loss
        and the mixed-precision gradients are fp32 already."""
        if self.pg.num_parts == 1:
            return t
        if not self._step_auto():
            return lax.psum(t, PARTS_AXIS)
        return jax.tree_util.tree_map(_psum_wide, t)

    def _gctx(self) -> GraphContext:
        """GraphContext for *inside* the shard_map body (local blocks)."""
        from ..train.trainer import resolve_head_chunk
        pgr = self.pg
        return GraphContext(
            head_chunk=resolve_head_chunk(self.config, pgr.part_nodes),
            edge_src=None, edge_dst=None, in_degree=None,  # filled per-call
            num_rows=pgr.part_nodes,
            gathered_rows=pgr.num_parts * pgr.part_nodes,
            gather_features=lambda x: lax.all_gather(
                x, PARTS_AXIS, axis=0, tiled=True),
            psum=self._psum_parts,
            aggr_impl=self.config.aggr_impl,
            symmetric=self.symmetric,
            halo=self.config.halo,
            ring_overlap=self.config.ring_overlap,
            sect_meta=self.data.sect_meta,
            flat8_win=self.data.flat_win,
            flat8_bands=self.data.flat_bands,
            bd_vpad=self.data.bd_vpad,
            bd_src_vpad=self.data.bd_src_vpad,
            # the DATA's group, validated == config at init: the
            # tables define what the kernel may assume
            bd_group=self.data.bd_group,
            total_rows=int(self._dataset.graph.num_nodes),
        )

    def _local_gctx(self, edge_src, edge_dst, in_degree, ell_idx,
                    ell_row_pos, ell_row_id, ring_idx, sect_idx,
                    sect_sub_dst, bd_tabs=(),
                    fuse_tabs=((), (), (), ()),
                    pid=None) -> GraphContext:
        """Local-block GraphContext for a shard_map body: slice the
        parts axis off every table.  attn_flat8 and flat_sum carry
        their single-section uniform tables in the sect slots
        (ShardedData docstring) and route them to the flat8 fields
        the builder reads (flat_sum's baked weight tables ride the
        sect_w slot -> flat8_w); bdense carries its residual there
        and its dense tiles in bd_tabs.  ``fuse_tabs`` = (ell_w,
        sect_w, ring_w, bd_scale) — the baked fused-normalization
        weights (empty tuples when unfused).

        ``pid`` (partial-auto 2-D steps only) is this block's traced
        partition index; it swaps ``gather_features`` for the
        psum-based halo gather — ``lax.all_gather`` over a manual
        axis aborts the SPMD partitioner when a GSPMD auto axis is
        present (_step_auto), but a psum of disjointly-placed local
        blocks is the same gathered matrix, and psum IS supported
        there.  ~2x the all-gather bytes on ICI; only the 2-D path
        pays it."""
        flat = self.config.aggr_impl in ("attn_flat8", "flat_sum")
        ell_w, sect_w, ring_w, bd_scale, *real = fuse_tabs
        extra = {}
        if real and real[0]:
            # ([P] int32,) with the parts axis collapsed to 1: this
            # partition's count, a scalar
            extra["real_rows"] = real[0][0][0]
        if pid is not None:
            extra["gather_features"] = functools.partial(
                _gather_by_psum, pid=pid, num_parts=self.pg.num_parts)
        return dc_replace(
            self._gctx(), edge_src=edge_src, edge_dst=edge_dst,
            in_degree=in_degree,
            ell_idx=tuple(a[0] for a in ell_idx),
            ell_row_pos=ell_row_pos[0],
            ell_row_id=tuple(a[0] for a in ell_row_id),
            ring_idx=tuple(a[0] for a in ring_idx),
            sect_idx=() if flat else tuple(a[0] for a in sect_idx),
            sect_sub_dst=(() if flat
                          else tuple(a[0] for a in sect_sub_dst)),
            # halo='ring' uploads empty sect stubs (the ring tables
            # fully describe the aggregation) — the flat8 fields must
            # stay None so the builder routes to ring_aggregate
            flat8_idx=sect_idx[0][0] if flat and sect_idx else None,
            flat8_dst=(sect_sub_dst[0][0]
                       if flat and sect_sub_dst else None),
            flat8_w=(sect_w[0][0]
                     if flat and sect_w else None),
            bd_a=bd_tabs[0][0] if bd_tabs else None,
            bd_src=bd_tabs[1][0] if bd_tabs else None,
            bd_dst=bd_tabs[2][0] if bd_tabs else None,
            ell_w=tuple(a[0] for a in ell_w),
            sect_w=() if flat else tuple(a[0] for a in sect_w),
            ring_w=ring_w[0][0] if ring_w else None,
            bd_scale=tuple(a[0] for a in bd_scale),
            **extra)

    def _build_train_step(self):
        mesh = self.mesh
        spec_p = P(PARTS_AXIS)
        spec_r = P()
        auto = self._step_auto()

        # the partial-auto variant takes one extra trailing arg: the
        # parts-sharded partition-index vector (``*pids``), standing
        # in for lax.axis_index which has no lowering under a GSPMD
        # auto axis (_step_auto).  The 1-D signature — and hence the
        # rigs' program keys — is untouched.
        def step(params, opt_state, feats, labels, mask, edge_src,
                 edge_dst, in_degree, ell_idx, ell_row_pos, ell_row_id,
                 ring_idx, sect_idx, sect_sub_dst, bd_tabs, fuse_tabs,
                 key, lr, *pids):
            # local blocks arrive with the parts axis collapsed to 1
            feats, labels, mask = feats[0], labels[0], mask[0]
            pid = pids[0][0] if pids else None
            gctx = self._local_gctx(
                edge_src[0], edge_dst[0], in_degree[0], ell_idx,
                ell_row_pos, ell_row_id, ring_idx, sect_idx,
                sect_sub_dst, bd_tabs, fuse_tabs, pid=pid)
            part_key = jax.random.fold_in(
                key, lax.axis_index(PARTS_AXIS) if pid is None else pid)

            # the running statistics ride in ``params`` but are no
            # parameters: gradient, all-reduce, Adam and the cast see
            # the rest (every partition computes the same new ones —
            # their moments are psum'd inside the op)
            params, state = split_state(params,
                                        self.model.state_names())

            def local_loss(p):
                # mixed precision: fp32 master params cast per step;
                # astype's vjp keeps grads (and the psum) in fp32
                with jax.named_scope(OPT_SCOPE):
                    p = {**cast_compute(p, self.compute), **state}
                logits, moved = self.model.apply_stateful(
                    p, feats, gctx, key=part_key, train=True,
                    remat=self.config.remat)
                with jax.named_scope(LOSS_SCOPE):
                    return masked_softmax_cross_entropy(
                        logits, labels, mask), moved

            (local_l, state), grads = jax.value_and_grad(
                local_loss, has_aux=True)(params)
            # the reference's replica-sum gradient allreduce
            # (optimizer_kernel.cu:88-94) as an ICI psum
            with jax.named_scope(ALLREDUCE_SCOPE):
                grads = self._psum_parts(grads)
                loss = self._psum_parts(local_l)
            with jax.named_scope(OPT_SCOPE):
                params, opt_state = adam_update(params, grads, opt_state,
                                                lr, self.adam_cfg)
            return {**params, **state}, opt_state, loss

        return _shard_map(
            step, mesh=mesh,
            in_specs=(spec_r, spec_r, spec_p, spec_p, spec_p, spec_p,
                      spec_p, spec_p, spec_p, spec_p, spec_p, spec_p,
                      spec_p, spec_p, spec_p, spec_p, spec_r, spec_r)
            + ((spec_p,) if auto else ()),
            out_specs=(spec_r, spec_r, spec_r),
            axis_names=frozenset({PARTS_AXIS}) if auto else frozenset())

    def _local_forward(self, params, feats, edge_src, edge_dst,
                       in_degree, ell_idx, ell_row_pos, ell_row_id,
                       ring_idx, sect_idx, sect_sub_dst, bd_tabs,
                       fuse_tabs=((), (), (), ()), pid=None):
        """Shared shard_map body: slice the parts axis off the local
        blocks, assemble the local GraphContext, run the inference
        forward — eval (adds metrics+psum) and predict (adds
        all_gather) both build on this, so the gctx wiring exists in
        ONE place.  ``pid`` threads the partial-auto partition index
        through to :meth:`_local_gctx`."""
        feats = feats[0]
        gctx = self._local_gctx(
            edge_src[0], edge_dst[0], in_degree[0], ell_idx,
            ell_row_pos, ell_row_id, ring_idx, sect_idx, sect_sub_dst,
            bd_tabs, fuse_tabs, pid=pid)
        with jax.named_scope(OPT_SCOPE):
            params = cast_compute(params, self.compute)
        return self.model.apply(params, feats, gctx, key=None,
                                train=False)

    def _build_eval_step(self):
        mesh = self.mesh
        spec_p = P(PARTS_AXIS)
        spec_r = P()
        auto = self._step_auto()

        def step(params, feats, labels, mask, *graph_args):
            pid = None
            if auto:
                # trailing parts-sharded partition-index vector, same
                # contract as the train step
                *graph_args, pids = graph_args
                pid = pids[0]
            logits = self._local_forward(params, feats, *graph_args,
                                         pid=pid)
            with jax.named_scope(LOSS_SCOPE):
                m = perf_metrics(logits, labels[0], mask[0])
            # (replicated metrics, sharded logits): predict() reuses
            # this program's logits output — no second compile, no
            # collective added to the eval path
            with jax.named_scope(ALLREDUCE_SCOPE):
                return jax.tree_util.tree_map(self._psum_parts,
                                              m), logits

        return _shard_map(
            step, mesh=mesh,
            in_specs=(spec_r, spec_p, spec_p, spec_p, spec_p, spec_p,
                      spec_p, spec_p, spec_p, spec_p, spec_p, spec_p,
                      spec_p, spec_p, spec_p)
            + ((spec_p,) if auto else ()),
            out_specs=(spec_r, spec_p),
            axis_names=frozenset({PARTS_AXIS}) if auto else frozenset())

    # ---- loop ----

    def train(self, epochs: Optional[int] = None) -> List[Dict[str, float]]:
        from ..train.trainer import run_epoch_loop

        def do_step(step_key, lr):
            # read self.data PER STEP, not once per train() call — an
            # epoch-boundary repartition swaps the sharded tables
            # mid-run and the next step must train on the new split
            d = self.data
            extra = () if self._pids is None else (self._pids,)
            self.params, self.opt_state, _ = self._train_step(
                self.params, self.opt_state, d.feats, d.labels,
                d.mask, d.edge_src, d.edge_dst, d.in_degree,
                d.ell_idx, d.ell_row_pos, d.ell_row_id, d.ring_idx,
                d.sect_idx, d.sect_sub_dst, d.bd_tabs,
                (d.ell_w, d.sect_w, d.ring_w, d.bd_scale, d.real_rows),
                step_key, lr, *extra)

        return run_epoch_loop(self, epochs, do_step, self.evaluate)

    def sync(self) -> None:
        """Block until all dispatched train steps have finished."""
        from ..utils.profiling import sync
        sync(self.params)

    def _run_eval_step(self):
        d = self.data
        extra = () if self._pids is None else (self._pids,)
        return self._eval_step(
            self.params, d.feats, d.labels, d.mask, d.edge_src,
            d.edge_dst, d.in_degree, d.ell_idx, d.ell_row_pos,
            d.ell_row_id, d.ring_idx, d.sect_idx, d.sect_sub_dst,
            d.bd_tabs,
            (d.ell_w, d.sect_w, d.ring_w, d.bd_scale, d.real_rows),
            *extra)

    def _eval(self, epoch: int) -> Dict[str, float]:
        # fetch ONLY the metrics: the shared eval/predict program also
        # outputs the sharded logits, which stay on device during
        # training evals
        m_dev, _ = self._run_eval_step()
        m = summarize_metrics(jax.device_get(m_dev))
        m["epoch"] = epoch
        return m

    def evaluate(self) -> Dict[str, float]:
        return self._eval(-1)

    def _padded_rows_of(self, node_ids) -> np.ndarray:
        """Original vertex ids → rows of the concatenated padded
        logits ([P * part_nodes, C] order): part ``p`` holds global
        range ``bounds[p]`` at local offset ``g - node_offset[p]``."""
        pg = self.pg
        ids = np.asarray(node_ids, dtype=np.int64).ravel()
        if ids.size and (ids.min() < 0 or ids.max() >= pg.num_nodes):
            raise ValueError(
                f"node ids out of range [0, {pg.num_nodes})")
        offs = np.asarray(pg.node_offset, dtype=np.int64)
        part = np.searchsorted(offs, ids, side="right") - 1
        return (part * pg.part_nodes + ids - offs[part]).astype(
            np.int32)

    def predict(self, node_ids=None) -> np.ndarray:
        """[V, C] inference-mode logits in ORIGINAL vertex order —
        the EVAL program's sharded logits output (one compiled program
        serves evaluate and predict; the old standalone predict step
        was a whole extra compile per config).  Single-controller
        meshes fetch the sharded result directly; multi-process meshes
        replicate it first through a tiny lazily-built all_gather
        program (a P('parts')-sharded device_get would touch
        non-addressable shards there) — rigs and tests never compile
        it.

        ``node_ids`` fetches only a row subset: the ids map to padded
        shard coordinates host-side and the rows are read PER SHARD
        from the addressable shard buffers — no device-side gather.
        The previous form dispatched ``jnp.take`` on the
        P('parts')-sharded logits, which made GSPMD all-gather the
        full [V, C] logits onto EVERY device before taking n rows —
        the dist-eval-gather full-width-materialization site the
        sharding auditor (analysis/sharding_lint.py) exists to
        catch; now only the shards holding requested rows cross
        device→host, O(V_p) each, and the request path adds no
        collective and no compiled program.  Under multi-process
        SPMD the rows are read from the replicated copy instead
        (non-addressable shards)."""
        _, logits = self._run_eval_step()
        if jax.process_count() > 1:
            if self._predict_gather is None:
                from ..obs.compile_watch import ObservedJit
                self._predict_gather = ObservedJit(
                    jitfn=jax.jit(self._build_predict_gather()),
                    name="dist_predict_gather",
                    verbose=self.config.verbose)
            logits = self._predict_gather(logits)
        if node_ids is not None:
            rows = self._padded_rows_of(node_ids)
            if jax.process_count() == 1:
                picked = self._rows_from_shards(logits, rows)
                if picked is not None:
                    return picked
            flat = np.asarray(jax.device_get(logits)).reshape(
                self.pg.padded_num_nodes, -1)
            return flat[rows]
        arr = np.asarray(jax.device_get(logits))
        arr = arr.reshape(self.pg.num_parts, self.pg.part_nodes, -1)
        return unpad_nodes(arr, self.pg)

    def _rows_from_shards(self, logits,
                          rows: np.ndarray) -> Optional[np.ndarray]:
        """Row subset of the P('parts')-sharded padded logits read
        per-shard: only shards that hold a requested row are fetched
        (O(V_p * C) device→host each), and nothing materializes on
        device.  None when the shard layout is not the expected 1-D
        padded-part split (caller falls back to a whole-array
        device_get — still collective-free)."""
        pn = self.pg.part_nodes
        C = int(logits.shape[-1])
        rows = np.asarray(rows, dtype=np.int64)
        want = set((rows // pn).tolist())
        hosts: Dict[int, np.ndarray] = {}
        try:
            for sh in logits.addressable_shards:
                idx = sh.index[0]
                start = idx.start or 0
                data = np.asarray(sh.data).reshape(-1, C)
                if data.shape[0] != pn or start % pn:
                    return None
                part = start // pn
                if part in want:
                    hosts[part] = data
        except (AttributeError, TypeError, IndexError):
            return None
        if not want.issubset(hosts):
            return None
        out = np.empty((rows.size, C), dtype=logits.dtype)
        for p in want:
            sel = (rows // pn) == p
            out[sel] = hosts[p][rows[sel] % pn]
        return out

    def _build_predict_gather(self):
        mesh = self.mesh
        spec_p = P(PARTS_AXIS)
        spec_r = P()

        def step(logits):
            # local [part_nodes, C] -> replicated [P, part_nodes, C]
            return lax.all_gather(logits, PARTS_AXIS, axis=0)

        # fully manual even on a 2-D mesh (NO auto axis): the logits
        # carry no model sharding, and all_gather over a manual axis
        # aborts the partitioner when an auto axis is present
        # (_step_auto) — manual over both axes just replicates the
        # gather across model replicas
        return _shard_map(step, mesh=mesh, in_specs=spec_p,
                          out_specs=spec_r)

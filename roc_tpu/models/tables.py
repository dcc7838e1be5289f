"""A layout's tables as one typed value.

Every aggregation layout (``core/ell.py AGGR_IMPLS``, the ring halo, a
typed graph's relation passes) keeps its device arrays in one frozen
dataclass registered as a pytree: the arrays are its children, in
field order, and what a program specializes on (section metadata,
window heights, tile bands, tile spaces) is its static aux data.  A
layout's fused-normalization weights are a field of its own table.

One builder, :func:`build_tables`, makes every layout's value.  What it
is built for is a :class:`View`: the rows summed into and the source
coordinates gathered from, in one of three forms — one device (global
ids, dummy ``V``), stacked parts (a part's padded rows, gathered ids,
dummy ``P * part_nodes``, one leading parts axis) or the parts one
process of a multi-host run holds (the same shapes, agreed by O(P)
collectives).  The stacked forms' per-partition view is
``tree_map(lambda a: a[0], tables)``, with the one-device value's tree
structure.

The ops read a value through its own methods — ``sum``, ``max``,
``windows`` — never by the layout's name.  The layout's name is read
here once, by :func:`build_tables` and :func:`tables_type`.  Those
methods call the kernels of ``ops/`` and the ring of
``parallel/ring.py``, so the module sits in the op layer beside
``builder.py GraphContext``, not in ``core/`` below them.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ell import (FLAT_SEG_ROWS, SECT_SEG_ROWS, build_ell,
                        clean_part_ptr, default_section_rows,
                        ell_shape_plan, ell_weight_tables,
                        flat_sum_from_graph, place_ell_part,
                        section_sub_counts, sectioned_from_graph,
                        sectioned_plan)
from ..core.partition import padded_edge_list
from ..core.relations import ORDER_PASSES
from ..obs.events import span
from ..ops.aggregate import (aggregate_ell, aggregate_ell_max,
                             aggregate_ell_sect, aggregate_flat_max,
                             aggregate_flat_sum, aggregate_segment,
                             scan_window_rows)
from ..ops.blockdense import (BLOCK, U4_MAX, aggregate_block_dense,
                              pack_a_u4, plan_blocks)
from ..ops.norm import inv_sqrt_degree_np
from ..parallel.ring import (build_ring_pairs, pack_ring_part,
                             ring_aggregate, ring_part_weights,
                             round_pair_edges)


def upload(tree, what: str, put=jnp.asarray):
    """Hand every array of the pytree ``tree`` to the device through
    ``put``, under one ``setup.upload`` span that counts the bytes
    handed over.  The span closes when ``put`` returns, which is when
    the host buffer has been handed over, not when the device holds
    it: it adds no sync."""
    with span("setup.upload", what=what) as s:
        def one(a):
            out = put(a)
            s["h2d_bytes"] += out.nbytes
            return out
        return jax.tree_util.tree_map(one, tree)


def _table(cls):
    """A frozen dataclass registered as a pytree: the fields marked
    :func:`_static` are aux data, the rest children in field order."""
    return jax.tree_util.register_dataclass(dataclass(frozen=True)(cls))


def _static(default):
    return field(default=default, metadata={"static": True})


class Tables:
    """What every layout's value answers; the defaults are a layout's
    with no baked weights and no scanned chunk tables."""

    # what the tables are called where a mismatch is reported
    what = ""

    @property
    def weighted(self) -> bool:
        """Whether the fused sum reads baked ``D^-1/2 A D^-1/2``
        weights (else it scales the features in the op)."""
        return False

    def sum(self, g, x: jax.Array, fused: bool = False) -> jax.Array:
        """``A_p @ gather(x)`` over these tables for the context
        ``g`` (``models/builder.py GraphContext``), its baked weights
        applied where ``fused``."""
        raise NotImplementedError(
            f"no sum aggregation for aggr_impl={g.aggr_impl!r}")

    def max(self, full: jax.Array, num_rows: int, layout: str):
        """The neighbour max out of the gathered ``full`` (dummy row
        last); rows with no neighbour read ``-inf``."""
        raise NotImplementedError(
            f"AGGR_MAX has no {layout!r} implementation; "
            "use aggr_impl='ell' (big graphs; sectioned carries "
            "no ELL tables and its additive carry can't max) or "
            "'segment' — the segment path materializes the full "
            "[E, F] per-edge matrix")

    def windows(self, gathered_rows: int):
        """``(idx, win_rows, bands, table rows)`` of every chunk table
        the sum scan walks (the run manifest's ``agg_window``)."""
        return []

    def pass_scans(self):
        """``(n_chunks, seg_rows, window, bands, table rows, sub-row
        width)`` of every relation pass a typed graph's scan walks."""
        return []

    # whether the tables hold the graph's edges, so stored edges over
    # their slots is their fill (``agg_slot_fill``)
    holds_edges = True


@_table
class EdgeList(Tables):
    """``segment``: the reference's edge list — ``src`` int32 ``[E]``
    in gathered coordinates (padding: the dummy row), ``dst`` int32
    ``[E]`` local rows, ascending."""

    src: Any
    dst: Any
    what = "edge list"

    def sum(self, g, x, fused=False):
        full = g.gathered_with_zero(x)
        return aggregate_segment(full, self.src, self.dst, g.num_rows)

    def max(self, full, num_rows, layout):
        dummy = full.shape[0] - 1
        neg = jnp.asarray(-jnp.inf, dtype=full.dtype)
        got = full[self.src]
        got = jnp.where((self.src != dummy)[:, None], got, neg)
        return jax.ops.segment_max(got, self.dst, num_segments=num_rows)


@_table
class EllTables(Tables):
    """``ell``: degree-bucketed tables (``core/ell.py EllTable``) —
    per bucket ``idx [rows_b, width_b]`` source ids, ``row_pos
    [rows]`` each row's place in the concatenated bucket output,
    per bucket ``row_id [rows_b]`` the row a bucket row sums into (what
    attention reads), per bucket ``w`` the baked weights (empty:
    unfused)."""

    idx: Tuple[Any, ...]
    row_pos: Any
    row_id: Tuple[Any, ...]
    w: Tuple[Any, ...] = ()
    what = "ELL"

    @property
    def weighted(self):
        return bool(self.w)

    def sum(self, g, x, fused=False):
        full = g.gathered_with_zero(x)
        if fused:
            return aggregate_ell(full, self.idx, self.row_pos,
                                 g.num_rows, ell_w=self.w)
        return aggregate_ell(full, self.idx, self.row_pos, g.num_rows)

    def max(self, full, num_rows, layout):
        # aggregate_ell_max row-segments large buckets under the
        # same 64 MiB budget as the sum path.
        return aggregate_ell_max(full, self.idx, self.row_pos, num_rows)


@_table
class SectionedTables(Tables):
    """``sectioned``: per source section ``idx [n_chunks, seg_rows,
    sub_w]`` section-local ids, ``sub_dst [n_chunks, seg_rows]`` the
    row each sub-row sums into, ``w`` the baked weights (empty:
    unfused); ``meta`` one static ``(start, size, win_rows, bands)``
    a section (``core/ell.py SectionedEll.meta``)."""

    idx: Tuple[Any, ...]
    sub_dst: Tuple[Any, ...]
    w: Tuple[Any, ...] = ()
    meta: Tuple[Tuple[Any, ...], ...] = _static(())
    what = "sectioned"

    @property
    def weighted(self):
        return bool(self.w)

    def sum(self, g, x, fused=False):
        return self.summed(g.gathered_with_zero(x), g.num_rows, fused)

    def summed(self, full, num_rows, fused=False):
        if fused:
            return aggregate_ell_sect(full, self.idx, self.sub_dst,
                                      self.meta, num_rows, sect_w=self.w)
        return aggregate_ell_sect(full, self.idx, self.sub_dst,
                                  self.meta, num_rows)

    def windows(self, gathered_rows):
        # a hand-made meta may stop at the section's size: no window
        return [(t, m[2], m[3] if len(m) > 3 else (), m[1] + 1)
                for t, m in zip(self.idx, self.meta) if len(m) > 2]


@_table
class FlatTables(Tables):
    """``flat_sum`` and ``attn_flat8``: ONE section spanning every
    source — ``idx [n_chunks, seg_rows, 8]`` gathered ids, ``dst
    [n_chunks, seg_rows]`` the row each sub-row sums into, ``w`` the
    baked weights (None: unfused).  The sum's static destination
    window ``win_rows`` (0: the whole carry) and tile ``bands`` are
    built for ``flat_sum`` only: attention and MAX never read them."""

    idx: Any
    dst: Any
    w: Any = None
    win_rows: int = _static(0)
    bands: Tuple[Tuple[int, int], ...] = _static(())
    what = "flat8 (uniform width-8)"

    @property
    def weighted(self):
        return self.w is not None

    def sum(self, g, x, fused=False):
        full = g.gathered_with_zero(x)
        if fused:
            return aggregate_flat_sum(full, self.idx, self.dst,
                                      g.num_rows, flat_w=self.w,
                                      win_rows=self.win_rows,
                                      bands=self.bands)
        return aggregate_flat_sum(full, self.idx, self.dst, g.num_rows,
                                  win_rows=self.win_rows,
                                  bands=self.bands)

    def max(self, full, num_rows, layout):
        # the uniform-scan MAX twin (ops/aggregate.py): one scan
        # program, scatter-max combine — the large-graph MAX path
        # the resolve pass routes to past FLAT_SUM_MIN_EDGES
        return aggregate_flat_max(full, self.idx, self.dst, num_rows)

    def windows(self, gathered_rows):
        if not self.win_rows:
            return []
        return [(self.idx, self.win_rows, self.bands, gathered_rows + 1)]


def _block_dense_flatten(t):
    # the parameter order the programs have always taken: the
    # residual's ids, the tiles, then the residual's weights and the
    # tiles' scales
    r = t.residual
    return ((r.idx, r.sub_dst, t.a, t.src, t.dst, r.w, t.scale),
            (r.meta, t.vpad, t.src_vpad, t.group))


def _block_dense_unflatten(aux, children):
    idx, sub_dst, a, src, dst, w, scale = children
    meta, vpad, src_vpad, group = aux
    return BlockDenseTables(
        residual=SectionedTables(idx=idx, sub_dst=sub_dst, w=w,
                                 meta=meta),
        a=a, src=src, dst=dst, scale=scale, vpad=vpad,
        src_vpad=src_vpad, group=group)


@dataclass(frozen=True)
class BlockDenseTables(Tables):
    """``bdense``: dense ``[128, 128]`` adjacency tiles on the MXU
    (``ops/blockdense.py``) — ``a [n_blocks, 128, 128 | 64]`` uint8 (or
    u4-packed) multiplicities, ``src``/``dst [n_blocks]`` tile ids
    (None: no tile reached ``min_fill``), ``scale`` the fused sum's
    ``(d_dst [vpad], d_src [src_vpad])`` tile scales (empty: unfused),
    and the scattered ``residual`` edges as sectioned tables (empty:
    every edge on a tile).  ``vpad`` / ``src_vpad`` are the destination
    and source tile spaces, ``group`` the blocks reduced an output-tile
    update."""

    residual: SectionedTables
    a: Any = None
    src: Any = None
    dst: Any = None
    scale: Tuple[Any, ...] = ()
    vpad: int = 0
    src_vpad: int = 0
    group: int = 1
    what = "block-dense"
    holds_edges = False

    @property
    def weighted(self):
        return bool(self.scale)

    def sum(self, g, x, fused=False):
        full = g.gathered_with_zero(x)
        out = None
        if self.a is not None:
            scales = ({"scale_dst": self.scale[0],
                       "scale_src": self.scale[1]} if fused else {})
            out = aggregate_block_dense(
                full, self.a, self.src, self.dst, g.num_rows, self.vpad,
                out_dtype=full.dtype, src_vpad=self.src_vpad,
                group=self.group, **scales)
        if self.residual.idx:
            res = self.residual.summed(full, g.num_rows, fused)
            out = res if out is None else out + res
        if out is None:  # zero-edge graph
            out = jnp.zeros((g.num_rows, full.shape[1]),
                            dtype=full.dtype)
        return out

    def windows(self, gathered_rows):
        return self.residual.windows(gathered_rows)


jax.tree_util.register_pytree_node(BlockDenseTables, _block_dense_flatten,
                                   _block_dense_unflatten)


@_table
class RingTables(Tables):
    """``halo='ring'``: flat per-(partition, source-shard) edge lists
    (``parallel/ring.py``) — ``src``/``dst [S, pair_edges]`` and
    ``w`` the baked weights (None: unfused).  They replace the
    layout's tables: the rotation drives the sum."""

    src: Any
    dst: Any
    w: Any = None
    what = "ring"

    @property
    def weighted(self):
        return self.w is not None

    def sum(self, g, x, fused=False):
        if fused:
            return ring_aggregate(x, self.src, self.dst,
                                  axis_name=g.axis_name, weights=self.w,
                                  overlap=g.ring_overlap)
        return ring_aggregate(x, self.src, self.dst,
                              axis_name=g.axis_name,
                              overlap=g.ring_overlap)

    def max(self, full, num_rows, layout):
        raise NotImplementedError(
            "AGGR_MAX is not supported with halo='ring' (the ring "
            "accumulator is additive); use halo='gather'")


@_table
class RelationTables(Tables):
    """A typed graph's relation passes (``core/relations.py``): one
    entry per pass the resolved orders run — ``meta`` static ``(pass
    name, rows summed into, rows gathered out of, win_rows, rels,
    bands)``, ``rels`` None for the table of every relation or the
    relation indices a cut layer sums (``Model.loss_cut``).  Scanned
    (``flat_sum``): ``idx [n_chunks, 8 * seg]`` slot-major, ``dst
    [n_chunks, seg]``, ``w`` float32 like ``idx`` (each slot's ``1 /
    deg_r(v)``); ``edge_list`` (``segment``): the forward passes' flat
    edge lists ``[E']``."""

    idx: Tuple[Any, ...]
    dst: Tuple[Any, ...]
    w: Tuple[Any, ...]
    meta: Tuple[Tuple[Any, ...], ...] = _static(())
    edge_list: bool = _static(False)
    what = "relation"

    def apply(self, x: jax.Array, name: str, rels) -> jax.Array:
        """One relation pass over ``x``, whose rows are what the pass
        gathers out of: the weighted sum into the pass's own row
        space, over the table of the relations ``rels`` (None: all)."""
        k = next(i for i, m in enumerate(self.meta)
                 if m[0] == name and m[4] == rels)
        _, out_rows, _, win, _, bands = self.meta[k]
        if self.edge_list:
            g = x[self.idx[k]] * self.w[k][:, None]
            return jax.ops.segment_sum(
                g, self.dst[k], num_segments=out_rows,
                indices_are_sorted=True).astype(x.dtype)
        zero = jnp.zeros((1, x.shape[1]), dtype=x.dtype)
        return aggregate_flat_sum(
            jnp.concatenate([x, zero], axis=0), self.idx[k],
            self.dst[k], out_rows, flat_w=self.w[k],
            win_rows=win, bands=bands, weights_fp32=True,
            slot_major=True)

    def slots(self):
        """``{(pass name, rels): slots a pass gathers}``."""
        return {(m[0], m[4]): int(np.prod(t.shape))
                for m, t in zip(self.meta, self.idx)}

    def pass_scans(self):
        if self.edge_list:
            return []
        return [(*d.shape, scan_window_rows(m[3], m[1] + 1), m[5],
                 m[2] + 1, 8) for m, d in zip(self.meta, self.dst)]


def _graph_flatten(t):
    # an edge list's own arrays before the in-degrees, any other
    # layout's tables after them: the order programs take them in
    lead = isinstance(t.tables, EdgeList)
    kids = ((t.tables, t.in_degree) if lead
            else (t.in_degree, t.tables))
    return kids + (t.real_rows,), lead


def _graph_unflatten(lead, children):
    a, b, real = children
    tables, deg = (a, b) if lead else (b, a)
    return GraphArrays(tables=tables, in_degree=deg, real_rows=real)


@dataclass(frozen=True)
class GraphArrays:
    """The graph's device arrays a program takes: one layout's
    ``tables``, the ``in_degree`` of the rows summed into and
    ``real_rows``, the count of a partition's real rows (None: every
    row is real) — in the parameter order programs have always had."""

    tables: Tables
    in_degree: Any
    real_rows: Any = None


jax.tree_util.register_pytree_node(GraphArrays, _graph_flatten,
                                   _graph_unflatten)


# ---------------------------------------------------------------- views


def _first(per_part: dict) -> np.ndarray:
    return np.asarray(next(iter(per_part.values())))


@dataclass
class View:
    """What a build's tables are for: ``rows`` summed into (a part's
    padded rows; ``V`` on one device) and ``src_rows`` gathered out of
    (also the dummy source id); every part's ``row_ptr [P, rows + 1]``,
    ``real_rows [P]`` and ``in_degree [P, rows]`` (O(V) metadata every
    process holds); ``parts``, those built here, whose source ids in
    gathered coordinates ``cols(p)`` gives.  ``edges(p)``: part
    ``p``'s padded edge list.  ``agree``: elementwise max over every
    part of per-part int vectors (a collective across processes).
    ``put``: per-part arrays to one device array — stacked on a
    leading parts axis, or (``stacked`` False: one device) the array
    itself.  ``plan`` / ``global_cols(p)``: the partition and its
    columns in global ids, which the ring's pairs read."""

    rows: int
    src_rows: int
    row_ptr: np.ndarray
    real_rows: np.ndarray
    in_degree: np.ndarray
    num_edges: int
    cols: Callable[[int], np.ndarray]
    edges: Callable[[int], Tuple[np.ndarray, np.ndarray]]
    put: Callable[..., Any]
    parts: Tuple[int, ...] = (0,)
    agree: Callable[[dict], np.ndarray] = _first
    stacked: bool = False
    plan: Any = None
    global_cols: Optional[Callable[[int], np.ndarray]] = None

    @classmethod
    def one_device(cls, graph, chunk: int = 512) -> "View":
        """Global ids, dummy ``V`` (the appended zero row); the edge
        list padded to the ``chunk`` multiple."""
        V = graph.num_nodes
        return cls(
            rows=V, src_rows=V, row_ptr=graph.row_ptr[None],
            real_rows=np.asarray([V]), in_degree=graph.in_degree[None],
            num_edges=graph.num_edges,
            cols=lambda p: graph.col_idx,
            edges=lambda p: padded_edge_list(graph, multiple=chunk),
            put=lambda per, what="tables": upload(per[0], what))

    def csr(self, p: int) -> Tuple[np.ndarray, np.ndarray]:
        """Part ``p``'s row pointers with padding edges dropped and its
        real edges' source ids."""
        ptr = clean_part_ptr(self.row_ptr[p], self.real_rows[p],
                             self.rows)
        return ptr, np.asarray(self.cols(p)[:int(ptr[-1])])


# ------------------------------------------------------------- builders


def _sectioned(view: View, csrs: dict, d, *, section_rows: int,
               seg_rows: int, sub_w: int = 8, u16: bool = False,
               label: str = "sectioned"):
    """The per-part sectioned tables of ``csrs`` (``{p: (ptr, col)}``)
    under one agreed chunk plan, their agreed windows and, with ``d``
    (inv-sqrt degrees, ``[P, rows]``), their baked weights:
    ``(idx, sub_dst, w, meta)``."""
    src_rows = view.src_rows
    with span("setup.tables", table=label,
              edges=view.num_edges) as s:
        counts = {p: section_sub_counts(ptr, col, view.rows, src_rows,
                                        section_rows, sub_w)
                  for p, (ptr, col) in csrs.items()}
        plan = sectioned_plan(view.agree(counts), seg_rows)
        sects = {p: sectioned_from_graph(
            ptr, col, view.rows, src_rows=src_rows,
            section_rows=section_rows, chunks_plan=plan,
            counts=counts[p], sub_w=sub_w)
            for p, (ptr, col) in csrs.items()}
        if u16:
            sects = {p: t.with_idx_dtype(np.uint16)
                     for p, t in sects.items()}
        s["sub_rows"] = sum(a.size for t in sects.values()
                            for a in t.sub_dst)
        # every part compiles one shape: each section's window and
        # each tile's band are the widest any part needs (the parts
        # share the chunk plan, hence the tiles)
        first = next(iter(sects.values()))
        agreed = [int(v) for v in view.agree({p: np.asarray(
            list(t.win_rows) + [b for tb in t.bands for _, b in tb],
            dtype=np.int64) for p, t in sects.items()})]
        rest = iter(agreed[len(first.win_rows):])
        bands = [tuple((tile, next(rest)) for tile, _ in tb)
                 for tb in first.bands]
        meta = tuple(zip(first.sec_starts, first.sec_sizes,
                         agreed[:len(first.win_rows)], bands))
    n = len(first.idx)
    idx = tuple(view.put({p: t.idx[k] for p, t in sects.items()})
                for k in range(n))
    sub_dst = tuple(view.put({p: t.sub_dst[k] for p, t in sects.items()})
                    for k in range(n))
    w = ()
    if d is not None:
        d_src = d.reshape(-1)
        with span("setup.tables",
                  table="flat_sum_w" if label == "flat_sum"
                  else "sect_w"):
            ws = {p: t.weight_tables(d[p], d_src)
                  for p, t in sects.items()}
        w = tuple(view.put({p: ws[p][k] for p in ws}) for k in range(n))
    return idx, sub_dst, w, meta


def _edge_list(view, d, **_):
    with span("setup.tables", table="edge_list", edges=view.num_edges):
        lists = {p: view.edges(p) for p in view.parts}
    return EdgeList(src=view.put({p: e[0] for p, e in lists.items()}),
                    dst=view.put({p: e[1] for p, e in lists.items()}))


def _ell(view, d, **_):
    with span("setup.tables", table="ell", edges=view.num_edges) as s:
        # the shape plan reads the degrees the bucket build sees, off
        # every part's row pointers (O(V), no column data)
        widths, rows_per_width = ell_shape_plan(view.row_ptr,
                                                view.real_rows)
        built = {}
        for p in view.parts:
            n = int(view.real_rows[p])
            ptr = view.row_ptr[p, :n + 1].astype(np.int64)
            built[p] = place_ell_part(
                build_ell(ptr, view.cols(p)), widths, rows_per_width,
                view.rows, view.src_rows)
        s["slots"] = sum(a.size for b in built.values() for a in b[0])
    nb = len(widths)
    idx = tuple(view.put({p: b[0][k] for p, b in built.items()})
                for k in range(nb))
    row_pos = view.put({p: b[1] for p, b in built.items()})
    row_id = tuple(view.put({p: b[2][k] for p, b in built.items()})
                   for k in range(nb))
    w = ()
    if d is not None:
        with span("setup.tables", table="ell_w"):
            ws = {p: ell_weight_tables(bidx, rid, d[p], d.reshape(-1))
                  for p, (bidx, _, rid) in built.items()}
        w = tuple(view.put({p: ws[p][k] for p in ws}) for k in range(nb))
    return EllTables(idx=idx, row_pos=row_pos, row_id=row_id, w=w)


def _sectioned_layout(view, d, *, sect_sub_w=8, sect_u16=False,
                      section_rows=None, **_):
    idx, sub_dst, w, meta = _sectioned(
        view, {p: view.csr(p) for p in view.parts}, d,
        section_rows=section_rows or default_section_rows(sect_u16),
        seg_rows=SECT_SEG_ROWS, sub_w=sect_sub_w, u16=sect_u16)
    return SectionedTables(idx=idx, sub_dst=sub_dst, w=w, meta=meta)


def _flat(view, d, *, attention=False, **_):
    # the uniform flat layout: ONE section spanning every source,
    # sub-rows of a row consecutive and ascending — compile size
    # independent of the degree distribution.  Two consumers of the
    # same tables: gat_aggregate_flat8 (attention) and
    # aggregate_flat_sum / _max (the sum / MAX consolidation).
    # FLAT_SEG_ROWS bounds the per-chunk transient [seg, 8, F] at
    # 64 MiB for F=256 fp32.
    idx, sub_dst, w, meta = _sectioned(
        view, {p: view.csr(p) for p in view.parts},
        None if attention else d, section_rows=view.src_rows,
        seg_rows=FLAT_SEG_ROWS, label="flat_sum")
    if attention:
        return FlatTables(idx=idx[0], dst=sub_dst[0])
    (_, _, win, bands), = meta
    return FlatTables(idx=idx[0], dst=sub_dst[0], w=w[0] if w else None,
                      win_rows=win, bands=bands)


def _block_dense(view, d, *, bdense_min_fill=64,
                 bdense_a_budget=2 << 30, bdense_group=1, census=None,
                 sect_sub_w=8, sect_u16=False, section_rows=None,
                 notes=None, **_):
    # per-part block-dense plans over the rectangular tile space (a
    # part's rows x gathered source coordinates), stacked to a uniform
    # block count: short parts pad with zero-A tiles scattered into
    # the dummy output tile, so every device runs one program
    csrs = {p: view.csr(p) for p in view.parts}

    def plans_at(budget):
        # group>1 plans arrive group-aligned, so the stacked tail
        # padding below extends in WHOLE dummy-dst groups
        return {p: plan_blocks(ptr, col, view.rows,
                               min_fill=bdense_min_fill,
                               a_budget_bytes=budget,
                               num_cols=view.src_rows,
                               group=bdense_group, census=census)
                for p, (ptr, col) in csrs.items()}

    def agreed(get):
        return int(view.agree({p: np.asarray([get(pl)], np.int64)
                               for p, pl in plans.items()})[0])

    # plan against DOUBLE the A budget first (pack_a_u4 halves the
    # device bytes), decided across parts: the stacked table needs one
    # trailing width, so every part packs or none; an unpackable plan
    # past the true budget is planned again at it
    with span("setup.tables", table="bdense",
              edges=view.num_edges) as s:
        plans = plans_at(bdense_a_budget * 2
                         if bdense_a_budget is not None else None)
        nblk = agreed(lambda pl: pl.n_blocks)
        packable = agreed(lambda pl: int(pl.a_blocks.max())
                          if pl.n_blocks else 0) <= U4_MAX
        if packable:
            plans = {p: pack_a_u4(pl) for p, pl in plans.items()}
        elif bdense_a_budget is not None \
                and nblk * BLOCK * BLOCK > bdense_a_budget:
            plans = plans_at(bdense_a_budget)
            nblk = agreed(lambda pl: pl.n_blocks)
        s["blocks"] = nblk
    if notes is not None:
        notes["bd_occupancy"] = tuple(pl.occupancy()
                                      for pl in plans.values())
    first = next(iter(plans.values()))
    tiles = {}
    if nblk:
        n_dst_tiles = first.vpad // BLOCK
        a_w = first.a_blocks.shape[-1]
        with span("setup.tables", table="bdense_stack"):
            for p, pl in plans.items():
                nb = pl.n_blocks
                a = np.zeros((nblk, BLOCK, a_w), dtype=np.uint8)
                src = np.zeros(nblk, dtype=np.int32)
                # padding blocks target the dummy output tile — zero A
                # keeps them inert, the dummy dst keeps even rounding
                # noise off real rows
                dst = np.full(nblk, n_dst_tiles, dtype=np.int32)
                a[:nb], src[:nb], dst[:nb] = (pl.a_blocks, pl.src_blk,
                                              pl.dst_blk)
                tiles[p] = (a, src, dst)
        tiles = dict(zip(("a", "src", "dst"), (
            view.put({p: t[k] for p, t in tiles.items()})
            for k in range(3))))
    # the stacked forms keep what their SPMD programs have always
    # taken: tile scales only where tiles exist, and a residual scan
    # even where every edge sits on a tile
    scale = ()
    if d is not None and (nblk or not view.stacked):
        # in-register tile scales: the integer A-table stays intact
        d_src = d.reshape(-1)
        dd, ds = {}, {}
        for p in plans:
            dd[p] = np.zeros(first.vpad, np.float32)
            dd[p][:view.rows] = d[p]
            ds[p] = np.zeros(first.src_vpad, np.float32)
            ds[p][:view.src_rows] = d_src
        scale = (view.put(dd), view.put(ds))
    residual = SectionedTables(idx=(), sub_dst=())
    if view.stacked or agreed(lambda pl: pl.res_col.shape[0]):
        with span("setup.tables", table="bdense_residual"):
            res = {p: (pl.res_row_ptr, pl.res_col)
                   for p, pl in plans.items()}
        idx, sub_dst, w, meta = _sectioned(
            view, res, d,
            section_rows=section_rows or default_section_rows(sect_u16),
            seg_rows=SECT_SEG_ROWS, sub_w=sect_sub_w, u16=sect_u16)
        residual = SectionedTables(idx=idx, sub_dst=sub_dst, w=w,
                                   meta=meta)
    return BlockDenseTables(
        residual=residual, scale=scale, vpad=first.vpad,
        src_vpad=first.src_vpad,
        group=bdense_group if nblk else 1, **tiles)


def _ring(view, d, *, notes=None, **_):
    # pair lists from each part's own columns; the uniform pair width
    # (an SPMD shape) is agreed over every part
    pg = view.plan
    P, pn = len(view.row_ptr), view.rows
    with span("setup.tables", table="ring"):
        pairs = {p: build_ring_pairs(pg, p, view.global_cols(p))
                 for p in view.parts}
        width = round_pair_edges(int(view.agree({p: np.asarray(
            [max((e.shape[0] for _, e in pairs[p].values()),
                 default=1)], np.int64) for p in view.parts})[0]))
        packed = {p: pack_ring_part(pairs[p], P, width, pn)
                  for p in view.parts}
    if notes is not None:
        notes["ring_padding_ratio"] = float(
            (P * P * width) / max(pg.num_edges, 1))
    w = None
    if d is not None:
        with span("setup.tables", table="ring_w"):
            w = {p: ring_part_weights(*packed[p], d, p)
                 for p in view.parts}
        w = view.put(w)
    return RingTables(src=view.put({p: s for p, (s, _) in packed.items()}),
                      dst=view.put({p: t for p, (_, t) in packed.items()}),
                      w=w)


def _relations(layout, view, typed, table_sets) -> RelationTables:
    """The typed graph's relation tables: per pass (``core/relations.py
    ORDER_PASSES``) of every ``(order, rels)`` table set, the flat
    width-8 table, its output rows and each slot's ``1 / deg_r(v)`` —
    the homogeneous flat builder over the pass's own CSR — or, under
    ``segment``, the forward passes' edge lists.  A cut set's passes
    mask the whole graph's sorted edges (``TypedGraph.restrict``)."""
    edge_list = layout == "segment"
    idx, dst, w, meta = [], [], [], []
    for order, rels in table_sets:
        t, cut = typed, "whole"
        if rels is not None:
            cut = "cut"
            with span("setup.tables", table="rel.cut.restrict"):
                t = typed.restrict(rels)
        fwd, bwd = ORDER_PASSES[order]
        for name in ((fwd,) if edge_list else (fwd, bwd)):
            with span("setup.tables", table=f"rel.{cut}.{name}") as s:
                row_ptr, col, n_into, n_out = t.pass_csr(name)
                if edge_list:
                    into = np.repeat(np.arange(n_into, dtype=np.int32),
                                     np.diff(row_ptr))
                    t_idx, t_dst, win, bands = col, into, 0, ()
                    t_w = t.slot_weights(name, col[:, None], into)[:, 0]
                else:
                    sect = flat_sum_from_graph(row_ptr, col, n_into,
                                               src_rows=n_out)
                    t_idx, t_dst = sect.idx[0], sect.sub_dst[0]
                    win, bands = sect.win_rows[0], sect.bands[0]
                    # slot-major at rest: [n_chunks, 8 * seg_rows]
                    n = t_idx.shape[0]
                    t_w = t.slot_weights(name, t_idx, t_dst).transpose(
                        0, 2, 1).reshape(n, -1)
                    t_idx = t_idx.transpose(0, 2, 1).reshape(n, -1)
                s.update(edges=col.shape[0], sub_rows=t_dst.size)
            for out, a in zip((idx, dst, w), (t_idx, t_dst, t_w)):
                out.append(view.put({0: a}))
            meta.append((name, n_into, n_out, win, rels, bands))
    return RelationTables(idx=tuple(idx), dst=tuple(dst), w=tuple(w),
                          meta=tuple(meta), edge_list=edge_list)


# layout name -> (its tables' type, its builder): the one place a
# layout's name meets its tables
_LAYOUTS = {
    "segment": (EdgeList, _edge_list),
    "ell": (EllTables, _ell),
    "sectioned": (SectionedTables, _sectioned_layout),
    "flat_sum": (FlatTables, _flat),
    "attn_flat8": (FlatTables,
                   lambda view, d, **kw: _flat(view, d, attention=True,
                                               **kw)),
    "bdense": (BlockDenseTables, _block_dense),
    "ring": (RingTables, _ring),
}


def _layout(layout: str):
    if layout not in _LAYOUTS:
        raise ValueError(
            f"no tables for aggr_impl={layout!r}: the layouts are "
            f"{', '.join(_LAYOUTS)}")
    return _LAYOUTS[layout]


def tables_type(layout: str) -> type:
    """The type of the tables ``layout`` (an ``aggr_impl``, or
    ``'ring'``) builds."""
    return _layout(layout)[0]


def build_tables(layout: str, view: View, *, fuse: bool = False,
                 relations=None, notes: Optional[Dict] = None,
                 **knobs) -> Tables:
    """The tables of ``layout`` (a resolved ``aggr_impl``, or
    ``'ring'``) for ``view``: each table's numpy build under a
    ``setup.tables`` span, every hand-over through ``view.put`` under
    ``setup.upload``.

    ``fuse``: bake the symmetric ``D^-1/2`` scales into the tables
    (weights shaped like the index tables, bdense tile scales) for a
    model rewritten by ``Model.fuse_norm_aggregate``; the attention
    tables take none.  ``relations`` ``(typed, table_sets)``: a typed
    graph's relation tables instead, scanned or (``segment``) edge
    lists.  ``knobs``: ``sect_sub_w`` / ``sect_u16`` / ``section_rows``
    (the sectioned tables, bdense's residual too; ``section_rows`` None:
    ``core/ell.py default_section_rows``), ``bdense_min_fill`` /
    ``bdense_a_budget`` / ``bdense_group`` / ``census`` (a probe's
    census of the same tile space, one device).  ``notes`` receives
    what the build measured for the host: ``bd_occupancy`` (one
    ``BlockPlan.occupancy()`` a part built here),
    ``ring_padding_ratio``."""
    if relations is not None:
        return _relations(layout, view, *relations)
    build = _layout(layout)[1]
    d = inv_sqrt_degree_np(view.in_degree) if fuse else None
    return build(view, d, notes=notes, **knobs)

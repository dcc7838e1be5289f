"""Degree-bucketed ELLPACK layout for TPU-friendly CSR aggregation.

The reference's hot loop is an irregular per-edge CSR walk with
shared-memory accumulators and atomics (``scattergather_kernel.cu:20-76``
via cub BlockScan).  TPUs have no atomics and XLA's scatter serializes,
so the rebuild uses a *regularized* layout instead:

- every row is assigned to a power-of-two **width bucket** covering its
  in-degree (min width 8, so padding waste is bounded by 2x plus the
  small-row floor);
- each bucket stores a dense ``[rows, width]`` matrix of source indices
  (padded entries point at the dummy zero-feature row);
- aggregation per bucket = ``feats[idx]`` (a large vectorized gather on
  contiguous feature rows) followed by a sum over the width axis — pure
  gather + reduce, lowering to TPU's native gather units and the VPU,
  with *no* scatter, *no* sequential scan over edge chunks, and *no*
  extra FLOPs;
- a static inverse permutation maps the concatenated bucket outputs back
  to local row order.

Buckets whose gathered block would exceed a memory budget are processed
in row segments via ``lax.scan`` (tens of iterations at Reddit scale, so
serialization is negligible).

For the distributed path, the bucket structure is made *uniform across
partitions* (same widths, same padded row counts) so the stacked arrays
shard over the 1-D parts mesh with identical static shapes per device.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import numpy as np


@dataclass
class EllTable:
    """Stacked per-partition ELL tables with uniform shapes.

    widths: static tuple of bucket widths (powers of two, ascending).
    idx: one array per bucket, int32 ``[P, rows_b, width_b]`` of source
      indices in *gathered-row coordinates* (dummy row = the appended
      zero row of the gathered feature matrix).
    row_pos: int32 ``[P, part_nodes]`` position of each local row in the
      concatenated bucket output; rows in no bucket (degree 0) point at
      the trailing zero slot (index == total bucket rows).
    row_id: one array per bucket, int32 ``[P, rows_b]`` — the LOCAL
      output row each bucket row aggregates into (the forward map;
      row_pos is its inverse).  Padding bucket rows carry
      ``part_nodes`` (a dummy slot).  Attention aggregation needs this
      to gather per-destination scores bucket-side (ops/attention.py);
      the plain sum path never reads it.
    """

    widths: Tuple[int, ...]
    idx: Tuple[np.ndarray, ...]
    row_pos: np.ndarray
    row_id: Tuple[np.ndarray, ...] = ()


def ell_weight_tables(idx, row_id, d_dst: np.ndarray,
                      d_src: np.ndarray) -> Tuple[np.ndarray, ...]:
    """Baked fused-normalization weights for one part's ELL buckets
    (``idx`` / ``row_id``, one array a bucket) — one fp32 array per
    bucket, shaped like ``idx``: ``w[r, j] = d_dst[row_id[r]] *
    d_src[idx[r, j]]`` (the per-edge entries of ``D^-1/2 A D^-1/2`` in
    ELL layout, so the fused aggregation needs ZERO runtime
    normalization — ops/aggregate.py aggregate_ell ``ell_w``).

    d_dst: [rows] inv-sqrt in-degrees of the part's output rows.
    d_src: [gathered_rows] the same in gathered-source coordinates
      (single-device: == d_dst; distributed: the padded global
      layout).  Padding bucket rows (``row_id == rows``) and padding
      entries (``idx == gathered_rows`` dummy) weigh 0.
    """
    dd = np.concatenate([np.asarray(d_dst, np.float32),
                         np.zeros(1, np.float32)])
    ds = np.concatenate([np.asarray(d_src, np.float32),
                         np.zeros(1, np.float32)])
    return tuple((dd[rid][:, None] * ds[i]).astype(np.float32)
                 for i, rid in zip(idx, row_id))


def row_widths(deg: np.ndarray, min_width: int) -> np.ndarray:
    """Per-row bucket width: smallest power-of-two >= degree (floored at
    ``min_width``); 0 for empty rows.  Widths are unbounded: a hub row
    of any degree gets its own wide bucket (the aggregation kernel
    scan-chunks large buckets, so memory stays bounded) — clamping
    would silently drop edges.  Fully vectorized (exact integer
    comparisons via a power table, no float log2)."""
    deg = np.asarray(deg)
    max_d = int(deg.max()) if deg.size else 1
    powers = [min_width]
    while powers[-1] < max_d:
        powers.append(powers[-1] * 2)
    powers = np.array(powers, dtype=np.int64)
    w = powers[np.searchsorted(powers, deg, side="left")]
    return np.where(deg > 0, w, 0).astype(np.int64)


def build_ell(local_row_ptr: np.ndarray, col_idx: np.ndarray,
              min_width: int = 8) -> dict:
    """Build one partition's bucket assignment from a local CSR.

    local_row_ptr: int [n+1] offsets into ``col_idx`` (callers pass the
    *real* row count so padding rows/edges are excluded).  Returns
    ``{width: (rows, idx)}`` with ``rows`` int64 [R_w] row ids and
    ``idx`` int32 [R_w, w] source indices (-1 padding to be replaced by
    the dummy id at stack time).  Vectorized — no per-row Python.
    """
    row_ptr = np.asarray(local_row_ptr, dtype=np.int64)
    deg = np.diff(row_ptr)
    widths = row_widths(deg, min_width)
    buckets: dict = {}
    for w in np.unique(widths[widths > 0]):
        w = int(w)
        rows = np.flatnonzero(widths == w)
        grid = np.arange(w, dtype=np.int64)[None, :]         # [1, w]
        valid = grid < deg[rows][:, None]                     # [R, w]
        flat = row_ptr[rows][:, None] + grid                  # [R, w]
        idx = np.full((rows.shape[0], w), -1, dtype=np.int32)
        idx[valid] = col_idx[flat[valid]]
        buckets[w] = (rows, idx)
    return buckets


def ell_shape_plan(part_row_ptr: np.ndarray, real_nodes: np.ndarray,
                   min_width: int = 8) -> Tuple[Tuple[int, ...], dict]:
    """Global uniform bucket shapes from row pointers alone (O(V)
    metadata — no column data), so multi-host processes can each build
    only their own partitions' tables (:func:`place_ell_part`) and still
    agree on the SPMD-required identical shapes.

    The plan MUST see the exact degrees :func:`build_ell` will see:
    ``np.diff(part_row_ptr[p, :n + 1])``.  These differ from the real
    in-degrees when ``real_nodes[p] == part_nodes`` — padding edges then
    have no padding row to live on and inflate the last real row's
    degree, so planning from real degrees would omit that row's
    (larger) bucket width and :func:`place_ell_part` would reject the
    table.

    Returns ``(widths, rows_per_width)`` where ``rows_per_width[w]`` is
    the max row count of bucket ``w`` over all partitions (floored at
    1 so shapes always exist)."""
    counts: dict = {}
    for p in range(part_row_ptr.shape[0]):
        n = int(real_nodes[p])
        if n == 0:
            continue
        deg = np.diff(part_row_ptr[p, :n + 1].astype(np.int64))
        w = row_widths(deg, min_width)
        for wv, c in zip(*np.unique(w[w > 0], return_counts=True)):
            counts[int(wv)] = max(counts.get(int(wv), 0), int(c))
    widths = tuple(sorted(counts)) or (min_width,)
    return widths, {w: max(counts.get(w, 0), 1) for w in widths}


def place_ell_part(buckets: dict, widths: Tuple[int, ...],
                   rows_per_width: dict, part_nodes: int,
                   dummy: int) -> Tuple[list, np.ndarray, list]:
    """Place one partition's buckets (from :func:`build_ell`) into the
    globally planned uniform shapes.  Returns ``(idx_arrays, row_pos,
    rid_arrays)`` with one int32 [rows_w, w] array per width, int32
    [part_nodes] output positions (zero slot == total planned rows),
    and the forward row map per bucket (int32 [rows_w], padding =
    ``part_nodes`` — see ``EllTable.row_id``).  Raises if the built
    buckets contain a width the plan lacks — a plan/build disagreement
    must fail loudly, not silently drop those rows' edges."""
    extra = set(buckets) - set(widths)
    if extra:
        raise ValueError(
            f"ELL plan/build mismatch: built bucket widths {sorted(extra)} "
            f"absent from planned widths {list(widths)} — the shape plan "
            "was derived from different degrees than the bucket build")
    idx_arrays = []
    rid_arrays = []
    total_rows = sum(rows_per_width[w] for w in widths)
    row_pos = np.full(part_nodes, total_rows, dtype=np.int32)
    offset = 0
    for w in widths:
        R = rows_per_width[w]
        arr = np.full((R, w), dummy, dtype=np.int32)
        rid = np.full(R, part_nodes, dtype=np.int32)
        if w in buckets:
            rows, idx = buckets[w]
            n = rows.shape[0]
            if n > R:
                raise ValueError(
                    f"ELL plan/build mismatch: bucket width {w} has {n} "
                    f"rows but the plan allows {R}")
            arr[:n] = np.where(idx >= 0, idx, dummy)
            rid[:n] = rows
            row_pos[rows] = offset + np.arange(n, dtype=np.int32)
        idx_arrays.append(arr)
        rid_arrays.append(rid)
        offset += R
    return idx_arrays, row_pos, rid_arrays


def ell_from_graph(row_ptr: np.ndarray, col_idx: np.ndarray,
                   num_nodes: int, min_width: int = 8) -> EllTable:
    """Single-device EllTable (P == 1); dummy = num_nodes (the appended
    zero row)."""
    row_ptr = np.asarray(row_ptr)
    widths, rows_per_width = ell_shape_plan(
        row_ptr[None], np.asarray([num_nodes]), min_width)
    idx, row_pos, row_id = place_ell_part(
        build_ell(row_ptr, np.asarray(col_idx), min_width=min_width),
        widths, rows_per_width, num_nodes, num_nodes)
    return EllTable(widths=widths, idx=tuple(a[None] for a in idx),
                    row_pos=row_pos[None],
                    row_id=tuple(a[None] for a in row_id))


@dataclass
class SectionedEll:
    """Source-sectioned width-8 sub-row layout — the fast-gather form.

    Measured on TPU v5 lite: a gather out of a table that stays in
    VMEM (<= ~64 MiB) costs a slot 3.18 ns at 256 bf16 lanes, whatever
    the chunk's height (PERF §5, PR 34 / 40), against 9.1-10.2 ns a
    row out of a whole table in HBM (products, PR 39) — so splitting
    the source rows into <= ``section_rows`` sections and rewriting
    every ELL row as width-8 sub-rows cut the Reddit-scale
    aggregation from 2006 ms to 865 ms (2.3x, July).  Since PR 41 a
    section's slots are summed by a kernel that holds the section in
    VMEM and never writes the gathered rows out (ops/aggregate.py
    ``_gather_sum_into``, PERF.md §6): 2.41 ms a Reddit chunk step of
    131,072 sub-rows at 256 lanes, where XLA's gather and reduce took
    4.65; the kernel now adds the sums into their destination rows
    itself.  Layout per section:

    - ``idx[s]``: int32 ``[n_chunks, seg_rows, 8]`` section-LOCAL source
      ids (dummy = the section's appended zero row); each original row's
      neighbors-in-section padded to a multiple of 8 and laid out as
      consecutive sub-rows;
    - ``sub_dst[s]``: int32 ``[n_chunks, seg_rows]`` the output row of
      each sub-row, ascending within each chunk (scatter-add with
      ``indices_are_sorted``); chunk padding points at ``num_rows``;
    - ``n_chunks`` and ``seg_rows`` are the section's own, read off its
      sub-row count by :func:`fit_chunks`: as few chunks as the cap on
      a chunk's height allows (:data:`SECT_SEG_ROWS`;
      :data:`FLAT_SEG_ROWS` for the flat layouts), each as tall as the
      section needs and no taller — a scan step costs its slots, not
      its edges, so chunk padding is paid for in full.  The arrays'
      shapes carry both; no field repeats them;
    - ``win_rows[s]``: how tall a chunk's run of real destinations can
      be in this section (:func:`chunk_window_rows` over ``sub_dst``;
      stacked tables: over every part, SPMD shapes must agree);
    - ``bands[s]``: beside it, how far apart the destinations of
      neighbouring sub-rows can lie: one ``(tile, band)`` pair per tile
      height of :data:`SEG_SUM_TILES` that divides the section's
      ``seg_rows`` — no run of ``tile`` consecutive sub-rows of a chunk
      reaches more than ``band`` destination rows
      (:func:`chunk_band_rows`; stacked tables: the max over parts).
      A section of a dense graph holds many sub-rows a destination
      row and its bands are a fraction of their tiles; that is what
      lets the scan sum a row's sub-rows on the MXU before the carry
      sees them (ops/aggregate.py ``scan_seg_sum``; the tables the
      kernel does not hold).

    The aggregation is a ``lax.scan`` over chunks carrying the output:
    a section the gather-sum kernel holds (``ops/aggregate.py
    gather_sum_form``) has the kernel sum the sub-rows out of the
    section slice and add each sum into its destination row, reading
    and writing only the carry's blocks the chunk reaches; any other
    table's ``[seg_rows, F]`` partials are scatter-added, sorted, into
    the ``[win_rows, F]`` window of the carry that starts at the
    chunk's first destination — the carry itself is only sliced and
    updated in place, so a chunk step costs its window, not the whole
    ``[num_rows, F]`` output (ops/aggregate.py ``_scan_window_sum``; a
    window past half the carry does not pay and scans the whole of it,
    ``scan_window_rows``).  Padding cost: each
    (row, section) pair rounds up to 8 — for avg section-degree d_s
    the overhead is
    <= 8/d_s + 4/d_s ~ a few percent at Reddit scale, but grows toward
    2x when d_s ~ 8 (many sections or low degree): prefer plain ELL
    for small graphs; this layout targets tables past VMEM size.
    """

    num_rows: int
    src_rows: int
    section_rows: int
    sec_starts: Tuple[int, ...]
    sec_sizes: Tuple[int, ...]
    idx: Tuple[np.ndarray, ...]
    sub_dst: Tuple[np.ndarray, ...]
    sub_w: int = 8
    win_rows: Tuple[int, ...] = ()
    bands: Tuple[Tuple[Tuple[int, int], ...], ...] = ()

    def __post_init__(self):
        # derived from the table, never configured: every builder
        # (native, numpy, stacked) gets both from the one pass here
        if not self.win_rows:
            self.win_rows = tuple(chunk_window_rows(d, self.num_rows)
                                  for d in self.sub_dst)
        if not self.bands:
            self.bands = tuple(chunk_bands(d, self.num_rows)
                               for d in self.sub_dst)

    @property
    def padded_edges(self) -> int:
        return sum(a.size for a in self.idx)

    @property
    def meta(self) -> Tuple[Tuple[Any, ...], ...]:
        """Static ``(start, size, win_rows, bands)`` per section — the
        ``sect_meta`` of :func:`roc_tpu.ops.aggregate.
        aggregate_ell_sect`."""
        return tuple(zip(self.sec_starts, self.sec_sizes,
                         self.win_rows, self.bands))

    def as_jax(self):
        """(idx, sub_dst, meta) in the calling convention of
        :func:`roc_tpu.ops.aggregate.aggregate_ell_sect` — the single
        conversion point for every consumer (trainer, benches)."""
        import jax.numpy as jnp
        return (tuple(jnp.asarray(a) for a in self.idx),
                tuple(jnp.asarray(a) for a in self.sub_dst),
                self.meta)

    def weight_tables(self, d_dst: np.ndarray,
                      d_src: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Baked fused-normalization weights — one fp32 array per
        section, shaped like ``idx``: ``w = d_dst[sub_dst] *
        d_src[start + idx]`` (the ``D^-1/2 A D^-1/2`` entries in
        sectioned layout; ops/aggregate.py aggregate_ell_sect
        ``sect_w``).

        d_dst: [num_rows] inv-sqrt in-degrees of the output rows, or
          stacked [P, num_rows] for per-part tables built by
          :func:`sectioned_from_padded_parts`.
        d_src: [src_rows] the same over source coordinates (gathered
          layout when they differ).  Chunk-padding sub-rows
          (``sub_dst == num_rows``) and padded entries (section-local
          dummy id == section size) weigh 0.
        """
        d_dst = np.asarray(d_dst, dtype=np.float32)
        d_src = np.asarray(d_src, dtype=np.float32)
        stacked = d_dst.ndim == 2
        zpad = (np.zeros((d_dst.shape[0], 1), np.float32) if stacked
                else np.zeros(1, np.float32))
        dd = np.concatenate([d_dst, zpad], axis=-1)
        out = []
        for st, sz, idx, sdst in zip(self.sec_starts, self.sec_sizes,
                                     self.idx, self.sub_dst):
            ds = np.concatenate([d_src[st:st + sz],
                                 np.zeros(1, np.float32)])
            if stacked:
                parts = np.arange(d_dst.shape[0])[:, None, None]
                wd = dd[parts, sdst]
            else:
                wd = dd[sdst]
            out.append((wd[..., None]
                        * ds[idx.astype(np.int64)]).astype(np.float32))
        return tuple(out)

    def with_idx_dtype(self, dtype) -> "SectionedEll":
        """Same layout with the index tables narrowed to ``dtype``
        (e.g. uint16 when every section's dummy id ``sec_size`` fits —
        section_rows <= 65535).  Halves the index-table HBM traffic;
        the gather semantics are unchanged."""
        info = np.iinfo(dtype)
        hi = max(self.sec_sizes)
        if hi > info.max:
            raise ValueError(
                f"section dummy id {hi} does not fit {np.dtype(dtype)} "
                f"(max {info.max}); build with section_rows <= "
                f"{info.max}")
        from dataclasses import replace
        return replace(
            self, idx=tuple(a.astype(dtype) for a in self.idx))


# Window heights round up to this many rows: a multiple of every
# dtype's sublane packing (8 fp32 / 16 bf16 rows a tile), and coarse
# enough that graphs of one shape share a compiled scan.
WIN_ROWS_MULTIPLE = 128


def chunk_window_rows(sub_dst: np.ndarray, num_rows: int) -> int:
    """Rows the chunk scan's destination window needs for
    ``sub_dst`` (``[..., n_chunks, seg_rows]``, ascending within a
    chunk, chunk padding == ``num_rows`` at the tail): the largest
    ``last real dst - first dst + 1`` over all chunks (and parts),
    rounded up to :data:`WIN_ROWS_MULTIPLE`.  Padding rows are not
    destinations — their partials are exactly zero (dummy source row,
    weight 0) — and an all-padding chunk needs no rows."""
    sub_dst = np.asarray(sub_dst)
    # a chunk is its own single tile
    first, last = _tile_spans(sub_dst, num_rows, sub_dst.shape[-1])
    span = int(np.maximum(last - first + 1, 0).max())
    return -(-max(span, 1) // WIN_ROWS_MULTIPLE) * WIN_ROWS_MULTIPLE


# Tile heights the chunk scan's segmented sum may cut a chunk into
# (ops/aggregate.py scan_seg_sum picks among those that divide the
# chunk's height), and what a band rounds up to: the bf16 sublane
# packing twice over, coarse enough that graphs of one shape share a
# compiled scan and fine enough that a band of 163 rows runs as one
# of 192, not 256.
SEG_SUM_TILES = (256, 512, 1024, 2048, 4096, 8192)
BAND_ROWS_MULTIPLE = 32


def _tile_spans(sub_dst: np.ndarray, num_rows: int, tile: int):
    """``(first, last)`` per tile of ``tile`` consecutive sub-rows:
    the tile's first destination and its last real one (-1: none)."""
    t = sub_dst.reshape(*sub_dst.shape[:-1], -1, tile)
    # ascending, padding at the tail: a tile's last sub-row is its
    # last destination unless the tile holds padding (a chunk's tail)
    last = t[..., -1].copy()
    pad = last >= num_rows
    if pad.any():
        tp = t[pad]
        last[pad] = np.where(tp < num_rows, tp, -1).max(axis=-1)
    return t[..., 0], last


def _band(first: np.ndarray, last: np.ndarray) -> int:
    span = int(np.maximum(last - first + 1, 0).max()) if first.size else 0
    return -(-max(span, 1) // BAND_ROWS_MULTIPLE) * BAND_ROWS_MULTIPLE


def chunk_band_rows(sub_dst: np.ndarray, num_rows: int, tile: int) -> int:
    """Rows the destinations of ``tile`` consecutive sub-rows of a
    chunk can span in ``sub_dst`` (``[..., n_chunks, seg_rows]``,
    ascending within a chunk, chunk padding == ``num_rows`` at the
    tail; ``tile`` divides ``seg_rows``): the largest ``last real dst
    - first dst + 1`` over every tile of every chunk (and part),
    rounded up to :data:`BAND_ROWS_MULTIPLE`.  Padding is no
    destination, and an all-padding tile spans nothing — the same
    convention as :func:`chunk_window_rows`, one level down."""
    return _band(*_tile_spans(np.asarray(sub_dst), num_rows, tile))


def chunk_bands(sub_dst: np.ndarray, num_rows: int
                ) -> Tuple[Tuple[int, int], ...]:
    """``(tile, chunk_band_rows(sub_dst, num_rows, tile))`` for every
    tile of :data:`SEG_SUM_TILES` that divides the chunk height, in
    ONE pass over ``sub_dst``: the smallest tile's spans are read off
    the table, each taller tile's are its two halves' (the first
    half's first row, the later of the two last ones)."""
    sub_dst = np.asarray(sub_dst)
    seg = sub_dst.shape[-1]
    tiles = [t for t in SEG_SUM_TILES if seg % t == 0]
    out = []
    for i, t in enumerate(tiles):
        if i == 0:
            first, last = _tile_spans(sub_dst, num_rows, t)
        else:
            first = first[..., ::2]
            last = np.maximum(last[..., ::2], last[..., 1::2])
        out.append((t, _band(first, last)))
    return tuple(out)


def max_bands(per_part) -> Tuple[Tuple[int, int], ...]:
    """One section's bands over its parts: tables stacked for SPMD
    share a chunk plan, hence their tiles, and must compile one
    shape, so each tile takes the widest band any part needs (as
    ``win_rows`` does)."""
    return tuple((tb[0][0], max(b for _, b in tb))
                 for tb in zip(*per_part))


# Uniform flat-sum layout (aggregate_flat_sum): the cap on a chunk's
# height in the single global section (fit_chunks derives the height
# under it).  8192 bounds the per-chunk gathered transient [seg, 8, F]
# at 64 MiB for F=256 fp32 — the same bound the attention flat8 tables
# use (they are the same layout).
FLAT_SEG_ROWS = 8192
# The cap on the sub-rows a chunk of a section's scan holds — not the
# height itself, which fit_chunks reads off the section's sub-row
# count.  The sweep below chose it at Reddit scale, 4.1M sub-rows a
# section, where a section of 18-32 chunks keeps the cap exactly.
SECT_SEG_ROWS = 131_072


def fit_chunks(sub_rows: int, cap: int) -> Tuple[int, int]:
    """``(n_chunks, seg_rows)`` of a section holding ``sub_rows``
    sub-rows under the cap ``cap`` on a chunk's height — the ONE place
    a chunk's height is decided (single-device, stacked and multihost
    builders; native and numpy).

    As few scan steps as the cap allows, ``n = ceil(sub_rows / cap)``,
    and each as tall as the section needs: ``ceil(sub_rows / n)``
    rounded up to ``cap // 16`` (a multiple of 8, the sublane tile;
    coarse enough that graphs of one size share a compiled scan).
    From sixteen chunks on that round-up restores the cap
    (``sub_rows / n > cap * (n - 1) / n >= cap * 15 / 16``), so a
    large section's tables are what a fixed height of ``cap`` gives:
    Reddit's 18-32 chunks of 131,072 and a products partition's 515
    of 8,192 are untouched.  A section of a few chunks stops gathering
    a last chunk that is mostly padding (ogbn-arxiv under
    ``sectioned``: 209,069 sub-rows in 2 x 106,496, not 2 x 131,072).
    A section that fits one chunk is as tall as its sub-rows, to the
    8: there is no second chunk to share a shape with."""
    n = max(1, -(-int(sub_rows) // cap))
    need = -(-int(sub_rows) // n)
    g = 8 if n == 1 else max(8, cap // 16)
    return n, min(cap, max(8, -(-need // g) * g))


def scan_chunk_rows(aggr_impl: str, num_edges: int) -> int:
    """Sub-rows one step of a width-8 scan layout gathers at most: the
    layout's cap on a chunk's height, or every sub-row of a graph
    smaller than one chunk; 0 for the layouts that scan no chunks.
    What the memory plan charges a step's scratch by
    (``core/memory.py``): the ``[rows, 8, F]`` gathered block and its
    ``[rows, F]`` sum.  The plan runs before any table exists, so it
    charges the cap: :func:`fit_chunks` never builds a taller chunk,
    and a section of a few chunks may run a shorter one."""
    rows = {"sectioned": SECT_SEG_ROWS, "bdense": SECT_SEG_ROWS,
            "flat_sum": FLAT_SEG_ROWS}.get(aggr_impl, 0)
    return min(rows, -(-num_edges // 64) * 8)

# Edge count past which the resolve pass routes an 'ell'-bound auto
# resolution to the uniform 'flat_sum' layout instead: the per-width
# bucket unroll compiles one gather/scan program per degree bucket
# (doubled by autodiff and multiplied by layers), which is what pushed
# products-scale first compiles past 15 min (ROADMAP compile wall);
# the flat layout compiles ONE scan shape per (dtype, F).  Same
# threshold as the attention path's ATTN_FLAT8_MIN_EDGES
# (train/trainer.py) — the two flat routes are the same fix.
FLAT_SUM_MIN_EDGES = 20_000_000


def flat_sum_from_graph(row_ptr: np.ndarray, col_idx: np.ndarray,
                        num_rows: int, src_rows: int = None,
                        seg_rows: int = FLAT_SEG_ROWS) -> SectionedEll:
    """The uniform flat-sum tables: a :class:`SectionedEll` with ONE
    section spanning all ``src_rows`` sources (ids global, dummy ==
    ``src_rows``, sub-rows of a row consecutive/ascending) — the
    layout :func:`roc_tpu.ops.aggregate.aggregate_flat_sum` scans.
    The same tables serve attention's flat8 layout (``models/tables.py
    FlatTables``): one builder, two consumers."""
    if src_rows is None:
        src_rows = num_rows
    return sectioned_from_graph(row_ptr, col_idx, num_rows,
                                src_rows=src_rows,
                                section_rows=src_rows,
                                seg_rows=seg_rows)


def flat_sum_from_padded_parts(part_row_ptr: np.ndarray,
                               part_col: np.ndarray,
                               real_nodes: np.ndarray,
                               part_nodes: int, src_rows: int,
                               seg_rows: int = FLAT_SEG_ROWS
                               ) -> SectionedEll:
    """Stacked per-part flat-sum tables (``[P, n_chunks, seg_rows, 8]``
    — SPMD-uniform shapes like every other stacked layout); the
    stacked twin of :func:`flat_sum_from_graph`."""
    return sectioned_from_padded_parts(
        part_row_ptr, part_col, real_nodes, part_nodes,
        src_rows=src_rows, section_rows=src_rows, seg_rows=seg_rows)


SECTION_ROWS_DEFAULT = 65_536   # 64 MiB of fp32 rows at F=256
# Swept on-chip at Reddit scale (v5e, F=256 bf16, 2026-07-30):
# section_rows 32768/65536/131072/262144 -> 826/776/808/1747 ms and
# seg_rows 65536/131072/262144/524288 -> 809/776/781/778 ms — the
# defaults sit at the measured optimum for BOTH dtypes (the residency
# window tracks row count, not table bytes: halving the bytes with
# bf16 does NOT move the best section size), and bf16 gains only
# ~11% on the aggregation itself (row-rate-bound gathers, ~7 ns/edge).

# Upper bound of the sectioned layout's winning range (v5e, F=256,
# median of 5; July, builder: BASELINE.md, 2026-07-30):
#   V=233k: sectioned 865 ms vs ell 2006 ms  (2.3x win)
#   V=500k: sectioned 440 ms vs ell 477 ms   (marginal win)
#   V=1M:   sectioned 964 ms vs ell 440 ms   (2.2x LOSS)
#   V=2.45M: sectioned 3784 ms vs ell 1010 ms (3.7x loss)
# That sweep predates the windowed chunk scan: its scatter-add rewrote
# the whole [V, F] carry every chunk step, which is what dominated past
# ~0.6M output rows and why 'auto' hands back to the whole-table
# gather there.  A chunk step now costs its [win_rows, F] window
# (SectionedEll.win_rows), so the bound is stale rather than measured:
# it keeps its value until sectioned is raced past it again (ROADMAP
# S1(b)).
SECTIONED_MAX_ROWS = 600_000

# The auto-impl window is a MEASURED property of a device generation,
# not of TPUs in general.  Rows are (section_rows lower bound,
# max out_rows upper bound); only generations with an on-chip sweep
# get a row, and an accelerator kind without one is an error, not a
# default (VERDICT r3 weak #5).  To calibrate a new generation: ONE
# command — ``python benchmarks/calibrate.py`` on the chip — races ell
# vs sectioned across a V-sweep and appends the measured row to
# ``benchmarks/calibration.json``, which this resolver merges over
# the builtin table (override path: ``ROC_TPU_CALIBRATION``).
SECTIONED_BOUNDS_BY_KIND = {
    "TPU v5 lite": (SECTION_ROWS_DEFAULT, SECTIONED_MAX_ROWS),
}


def default_section_rows(sect_u16: bool = False) -> int:
    """Default section size for the sectioned layout; uint16
    section-local ids need the dummy id (== section size) to fit in
    the dtype.  The ONE place for that rule — every view's sectioned
    tables (``models/tables.py build_tables``) read it."""
    return min(SECTION_ROWS_DEFAULT, 65_535) if sect_u16 \
        else SECTION_ROWS_DEFAULT


def calibration_path() -> str:
    """Location of the measured-bounds JSON (calibrate.py writes it,
    sectioned_bounds reads it)."""
    return os.environ.get(
        "ROC_TPU_CALIBRATION",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            "benchmarks", "calibration.json"))


def _calibrated_rows() -> dict:
    """device_kind -> (lo, hi) rows measured by benchmarks/calibrate.py.
    Missing/corrupt file == no extra rows (the builtin table still
    applies); the file is tiny and read per resolve, so a fresh
    calibration takes effect without a restart."""
    try:
        import json
        with open(calibration_path()) as f:
            db = json.load(f)
        return {k: (int(v["lo"]), int(v["hi"]))
                for k, v in db.items()
                if isinstance(v, dict) and "lo" in v and "hi" in v}
    except (OSError, ValueError, TypeError):
        return {}


def sectioned_bounds(device_kind: Optional[str] = None
                     ) -> Tuple[int, int]:
    """(lower num_nodes bound, upper out_rows bound) of the sectioned
    layout's winning window for ``device_kind`` (default:
    $ROC_TPU_DEVICE_KIND, else the current backend's first device).
    The CPU backend — tests and the virtual-device rigs — resolves
    with the v5e numbers so its programs match the chip's; any other
    kind without a measured row raises."""
    if device_kind is None:
        device_kind = os.environ.get("ROC_TPU_DEVICE_KIND")
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    calibrated = _calibrated_rows()
    if device_kind in calibrated:
        return calibrated[device_kind]
    if device_kind in SECTIONED_BOUNDS_BY_KIND:
        return SECTIONED_BOUNDS_BY_KIND[device_kind]
    if device_kind == "cpu":
        return SECTION_ROWS_DEFAULT, SECTIONED_MAX_ROWS
    raise ValueError(
        f"no measured sectioned-window bounds for device kind "
        f"{device_kind!r} (known: {sorted(SECTIONED_BOUNDS_BY_KIND)} "
        f"+ {calibration_path()}); run benchmarks/calibrate.py on it "
        f"or pass --impl explicitly")


# What ``--impl`` / ``TrainConfig.aggr_impl`` may name: ``auto``, the
# edge-list reference ``segment``, and the layouts a resolver can
# return (tests/test_auto_impl.py holds each to a route).
AGGR_IMPLS = ("auto", "segment", "ell", "sectioned", "bdense", "flat_sum")


def check_stored_aggr_impl(name, where: str) -> None:
    """An ``aggr_impl`` read back from outside the program (an export
    manifest, a checkpoint's fingerprint) must name a layout that
    exists: the six of :data:`AGGR_IMPLS`, or ``attn_flat8``, which
    only a resolved attention config carries."""
    if name not in AGGR_IMPLS + ("attn_flat8",):
        raise ValueError(
            f"{where}: stored aggr_impl={name!r} names no aggregation "
            f"layout of this build (removed or unknown); --impl takes "
            f"{', '.join(AGGR_IMPLS)} — export or train again under "
            f"one of them")


def resolve_auto_impl(num_nodes: int,
                      out_rows: Optional[int] = None,
                      device_kind: Optional[str] = None,
                      num_edges: Optional[int] = None) -> str:
    """The data-driven ``aggr_impl='auto'`` split — ONE place for the
    rule (trainer, distributed, bench, model zoo all call this):
    ``sectioned`` in its measured winning window, ``flat_sum`` for
    ell-bound graphs past :data:`FLAT_SUM_MIN_EDGES` (the compile-wall
    route: one uniform scan program instead of one program per degree
    bucket), ``ell`` otherwise.

    The two sectioned bounds scale with different sizes: the LOWER
    bound is the gathered source-table size (global ``num_nodes`` —
    sectioned's win is VMEM-resident section gathers, and a partition
    gathers from ALL nodes), while the UPPER bound is the output
    carry ``[out_rows, F]`` — per-partition ``out_rows`` in
    distributed runs (defaults to ``num_nodes`` single-device) — and
    dates from when every chunk step rewrote all of it; the scan now
    touches a ``win_rows`` window a step, so that bound awaits a new
    sweep (see :data:`SECTIONED_MAX_ROWS`).  The bounds are
    generation-keyed (:func:`sectioned_bounds`).  ``num_edges=None``
    skips the flat_sum route (legacy callers keep the old
    sectioned/ell split)."""
    if out_rows is None:
        out_rows = num_nodes
    lo, hi = sectioned_bounds(device_kind)
    if num_nodes > lo and out_rows <= hi:
        return "sectioned"
    if num_edges is not None and num_edges >= FLAT_SUM_MIN_EDGES:
        # outside sectioned's window the fallback used to be the
        # per-bucket ELL unroll — at this edge count its compile cost
        # (one program per width bucket x autodiff x layers) dominates
        # the first-run wall; the uniform flat layout compiles ONE
        # scan shape and gathers from the same whole table, so the
        # runtime is ell-class while the program space is O(1)
        return "flat_sum"
    return "ell"


# The TPU's vector registers, and the minor tile of every HBM layout,
# are 128 lanes wide.
LANE_WIDTH = 128


def agg_lane_width(feat_width: int, aggr_impl: str,
                   halo: str = "gather") -> int:
    """Feature width a sum aggregation runs at — ONE place for the
    rule, decided from what the code sees in its input (the operand's
    width, the layout, the halo mode): ``flat_sum`` narrower than the
    128 lanes runs at 128, everything else at its own width.
    ``GraphContext.aggregate_sum`` / ``aggregate_fused`` zero-pad the
    operand's feature axis to it and slice the result back; the spare
    lanes hold zeros, so the real columns are the same sums in the
    same order, bit for bit.

    Why ``flat_sum``: its chunk scan gathers out of the whole
    ``[G+1, F]`` table in HBM, and XLA stores such a table with the
    *vertex* axis minor when ``F < 128`` — one gathered row then
    touches ``F`` tiles; at 128 the table is row-major and a row is
    one tile line.  Why not ``sectioned`` (nor ``bdense``, whose
    residual is ``sectioned``'s scan): it gathers out of a section
    block staged in VMEM, which XLA lane-pads by itself, so the pad
    buys nothing there — raced on the v5e at Reddit shape, 41 against
    128 wide (my chip run, PR 32): the op 944.8 ms an epoch both ways,
    its chunk gather 1.93 / 1.94 ms, ``epoch_ms`` 2,275.06 / 2,275.14,
    ``peak_hbm_gib`` 13.063 both.  ``ell`` as a sum layout,
    ``segment``, the ``ring`` halo and the MAX scans have no cell and
    no measurement, and stay unpadded.  The products numbers are in
    PERF §6, PR 32."""
    if (aggr_impl == "flat_sum" and halo == "gather"
            and feat_width < LANE_WIDTH):
        return LANE_WIDTH
    return feat_width


def section_sub_counts(row_ptr: np.ndarray, col_idx: np.ndarray,
                       num_rows: int, src_rows: int,
                       section_rows: int = SECTION_ROWS_DEFAULT,
                       sub_w: int = 8) -> np.ndarray:
    """Per-section sub-row totals (the cheap metadata pass used to
    agree on uniform chunk counts across SPMD partitions/hosts).
    Native single-pass when librocio is available; numpy bincounts
    otherwise."""
    from .. import native
    row_ptr = np.asarray(row_ptr)
    col_idx = np.asarray(col_idx)
    n_sec = max(1, -(-src_rows // section_rows))
    if native.available():
        return native.sectioned_counts(row_ptr, col_idx, num_rows,
                                       section_rows, n_sec, sub_w)
    dst_all = np.repeat(np.arange(num_rows, dtype=np.int64),
                        np.diff(row_ptr))
    sec_of = col_idx.astype(np.int64) // section_rows
    out = np.zeros(n_sec, dtype=np.int64)
    for s in range(n_sec):
        cnt = np.bincount(dst_all[sec_of == s], minlength=num_rows)
        out[s] = int((-(-cnt // sub_w)).sum())
    return out


def _resolve_chunks(counts, seg_rows: int, chunks_plan,
                    first_section: int = 0) -> list:
    """Per-section ``(n_chunks, seg_rows)`` from sub-row totals:
    :func:`fit_chunks` under the cap ``seg_rows``, or the SPMD plan's
    entry, validated against the section's own total — the ONE place
    this logic lives (native and numpy builders both call it)."""
    out = []
    for i, c in enumerate(counts):
        s = first_section + i
        if chunks_plan is None:
            out.append(fit_chunks(c, seg_rows))
            continue
        n, seg = (int(v) for v in chunks_plan[s])
        if int(c) > n * seg:
            raise ValueError(
                f"section {s}: {int(c)} sub-rows > planned {n} chunks "
                f"of {seg} — the plan must come from "
                f"section_sub_counts over the same edges")
        out.append((n, seg))
    return out


def sectioned_from_graph(row_ptr: np.ndarray, col_idx: np.ndarray,
                         num_rows: int, src_rows: int = None,
                         section_rows: int = SECTION_ROWS_DEFAULT,
                         seg_rows: int = SECT_SEG_ROWS,
                         chunks_plan=None, counts=None,
                         sub_w: int = 8) -> SectionedEll:
    """Build the sectioned layout from a dst-major CSR.

    ``src_rows`` is the source-id space (defaults to ``num_rows``;
    the distributed gathered space when they differ).  ``section_rows``
    defaults to 64 MiB worth of fp32 rows at F=256 — pass less for
    wider feature matrices.  ``seg_rows`` caps a chunk's height; each
    section's chunk count and height come from its own sub-row total
    (:func:`fit_chunks`).  ``chunks_plan`` (per-section ``(n_chunks,
    seg_rows)``, :func:`sectioned_plan` of :func:`section_sub_counts`
    maxed across partitions) forces uniform shapes for SPMD stacking
    instead; a section holding more sub-rows than its plan raises.
    ``sub_w`` is the sub-row width (neighbors
    gathered per table row; each (row, section) pair pads to a
    multiple of it).  Host-side prep uses the native two-pass builder
    (native/rocio.cc roc_sectioned_counts/_fill: 1.1 s at Reddit
    scale, byte-identical tables — 45x the numpy fallback's ~49 s)
    when librocio is available.
    """
    row_ptr = np.asarray(row_ptr)
    col_idx = np.asarray(col_idx)
    if src_rows is None:
        src_rows = num_rows
    n_sec = max(1, -(-src_rows // section_rows))
    all_sizes = [min(section_rows, src_rows - s * section_rows)
                 for s in range(n_sec)]
    from .. import native
    if native.available():
        # native two-pass fill (counts -> plan -> fill): 45x the numpy
        # path at Reddit scale and byte-identical tables (tested).
        # counts= lets plan-building callers (sectioned_from_padded_
        # parts, models/tables.py) skip the second CSR walk.
        if counts is None:
            counts = native.sectioned_counts(row_ptr, col_idx, num_rows,
                                             section_rows, n_sec, sub_w)
        chunks = _resolve_chunks(counts, seg_rows, chunks_plan)
        slots = np.asarray([n * seg for n, seg in chunks],
                           dtype=np.int64)
        idx_flat, sub_flat = native.sectioned_fill(
            row_ptr, col_idx, num_rows, section_rows,
            np.asarray(all_sizes, dtype=np.int64), slots, sub_w)
        idxs, dsts, off = [], [], 0
        for s in range(n_sec):
            n = int(slots[s])
            idxs.append(idx_flat[off:off + n].reshape(
                *chunks[s], sub_w))
            dsts.append(sub_flat[off:off + n].reshape(chunks[s]))
            off += n
        return SectionedEll(
            num_rows=num_rows, src_rows=src_rows,
            section_rows=section_rows,
            sec_starts=tuple(s * section_rows for s in range(n_sec)),
            sec_sizes=tuple(all_sizes),
            idx=tuple(idxs), sub_dst=tuple(dsts), sub_w=sub_w)
    dst_all = np.repeat(np.arange(num_rows, dtype=np.int64),
                        np.diff(row_ptr))
    src_all = col_idx.astype(np.int64)
    sec_of = (src_all // section_rows).astype(np.int8 if n_sec < 128
                                              else np.int32)
    starts, sizes, idxs, dsts = [], [], [], []
    for s in range(n_sec):
        sel = sec_of == s
        srcs = (src_all[sel] - s * section_rows).astype(np.int32)
        dst = dst_all[sel]
        cnt = np.bincount(dst, minlength=num_rows)
        padded = -(-cnt // sub_w) * sub_w
        nz = np.flatnonzero(padded)
        sub_rows = padded[nz] // sub_w
        total_sub = int(sub_rows.sum())
        sec_size = all_sizes[s]
        n_chunks, seg = _resolve_chunks(
            [total_sub], seg_rows, chunks_plan, first_section=s)[0]
        pad = n_chunks * seg - total_sub
        tbl = np.full((n_chunks * seg, sub_w), sec_size,
                      dtype=np.int32)
        start_sub = np.zeros(len(nz) + 1, dtype=np.int64)
        np.cumsum(sub_rows, out=start_sub[1:])
        grp_start = np.zeros(num_rows + 1, dtype=np.int64)
        np.cumsum(cnt, out=grp_start[1:])
        off = np.arange(dst.shape[0], dtype=np.int64) - grp_start[dst]
        act_of = np.zeros(num_rows, dtype=np.int64)
        act_of[nz] = np.arange(len(nz))
        tbl.reshape(-1)[start_sub[act_of[dst]] * sub_w + off] = srcs
        sub_dst = np.concatenate(
            [np.repeat(nz, sub_rows),
             np.full(pad, num_rows, np.int64)]).astype(np.int32)
        starts.append(s * section_rows)
        sizes.append(sec_size)
        idxs.append(tbl.reshape(n_chunks, seg, sub_w))
        dsts.append(sub_dst.reshape(n_chunks, seg))
    return SectionedEll(
        num_rows=num_rows, src_rows=src_rows,
        section_rows=section_rows,
        sec_starts=tuple(starts), sec_sizes=tuple(sizes),
        idx=tuple(idxs), sub_dst=tuple(dsts), sub_w=sub_w)


def sectioned_plan(counts_max: np.ndarray,
                   seg_rows: int = SECT_SEG_ROWS) -> list:
    """Per-section ``(n_chunks, seg_rows)`` from elementwise-maxed
    per-partition sub-row counts — THE single place the uniform-shape
    agreement math lives (used by every view of ``models/tables.py
    build_tables`` and by :func:`sectioned_from_padded_parts`; a
    divergence would only surface as a chunks_plan error at scale).
    :func:`fit_chunks` of each section's largest part under the cap
    ``seg_rows``, so every part keeps one shape."""
    return [fit_chunks(c, seg_rows) for c in np.asarray(counts_max)]


def clean_part_ptr(part_row_ptr: np.ndarray, real_nodes: int,
                   part_nodes: int) -> np.ndarray:
    """One partition's row pointers with padding edges dropped: rows
    past ``real_nodes`` become empty instead of carrying the padded
    edge tail."""
    n = int(real_nodes)
    ptr = part_row_ptr[:n + 1].astype(np.int64)
    return np.concatenate(
        [ptr, np.full(part_nodes - n, ptr[n], dtype=np.int64)])


def sectioned_from_padded_parts(part_row_ptr: np.ndarray,
                                part_col: np.ndarray,
                                real_nodes: np.ndarray,
                                part_nodes: int, src_rows: int,
                                section_rows: int = SECTION_ROWS_DEFAULT,
                                seg_rows: int = SECT_SEG_ROWS,
                                sub_w: int = 8) -> SectionedEll:
    """Uniform stacked per-part sectioned tables for the SPMD step:
    ``idx[s]`` is ``[P, n_chunks_s, seg_rows, sub_w]`` and
    ``sub_dst[s]`` ``[P, n_chunks_s, seg_rows]`` — same static shapes
    on every device.
    Each section's chunk count and height fit its largest part
    (metadata pass + :func:`sectioned_plan`, under the cap
    ``seg_rows``), so partitions with fewer edges carry padding
    sub-rows that gather the section's zero row into the dummy output
    row.

    ``part_col`` is ``[P, part_edges]`` in gathered-row coordinates;
    padding edges are excluded via the real row extents."""
    P = part_row_ptr.shape[0]
    ptrs = [clean_part_ptr(part_row_ptr[p], real_nodes[p], part_nodes)
            for p in range(P)]
    cols = [np.asarray(part_col[p][:int(ptrs[p][-1])])
            for p in range(P)]
    counts = np.stack([
        section_sub_counts(ptrs[p], cols[p], part_nodes, src_rows,
                           section_rows, sub_w) for p in range(P)])
    plan = sectioned_plan(counts.max(axis=0), seg_rows)
    per_part = [
        sectioned_from_graph(ptrs[p], cols[p], part_nodes,
                             src_rows=src_rows,
                             section_rows=section_rows,
                             chunks_plan=plan,
                             counts=counts[p], sub_w=sub_w)
        for p in range(P)]
    first = per_part[0]
    return SectionedEll(
        num_rows=part_nodes, src_rows=src_rows,
        section_rows=section_rows,
        sec_starts=first.sec_starts, sec_sizes=first.sec_sizes,
        idx=tuple(np.stack([pp.idx[s] for pp in per_part])
                  for s in range(len(first.idx))),
        sub_dst=tuple(np.stack([pp.sub_dst[s] for pp in per_part])
                      for s in range(len(first.sub_dst))),
        sub_w=sub_w,
        win_rows=tuple(max(pp.win_rows[s] for pp in per_part)
                       for s in range(len(first.win_rows))),
        bands=tuple(max_bands([pp.bands[s] for pp in per_part])
                    for s in range(len(first.bands))))

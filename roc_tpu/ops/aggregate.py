"""Neighbor aggregation (the reference's ScatterGather op).

Reference semantics (``scattergather_kernel.cu:20-76``): for a dst-major
CSR, ``out[dst] = sum_{(src,dst) in E} in[src]`` — a CSR-SpMM with an
implicit all-ones sparse matrix.  The reference backward *reuses the
forward kernel* on the same CSR (``scattergather_kernel.cu:160-170``),
which is correct only for symmetric adjacency; we get the exact transpose
for free from JAX autodiff (gather/segment_sum differentiate to the
scatter/gather pair), so our gradients are correct for any graph while
matching the reference bit-for-bit on the symmetric graphs it supports.

One semantics, one edge-list form and the table layouts ``auto``
chooses among (``core/ell.py resolve_auto_impl``,
``train/trainer.py resolve_auto_impl_probed``):

- ``segment`` (:func:`aggregate_segment`): one-shot gather +
  ``segment_sum`` over the edge list.  Materializes the ``[E, F]``
  per-edge feature matrix — fine for small graphs, and the numerics
  reference every parity test compares against.
- ``ell`` (:func:`aggregate_ell`): degree-bucketed ELLPACK tables, one
  gather + width reduction per bucket; also what attention and the
  ELL MAX (:func:`aggregate_ell_max`) read.
- ``sectioned`` (:func:`aggregate_ell_sect`) and ``flat_sum``
  (:func:`aggregate_flat_sum`, MAX twin :func:`aggregate_flat_max`):
  width-8 sub-row tables walked by one chunk scan
  (:func:`_scan_window_sum`), per source section or over one global
  section.
- ``bdense`` (ops/blockdense.py): dense adjacency tiles on the MXU,
  the residual edges through ``sectioned``.

All take source ids in *gathered* coordinates and produce rows for the
local destination range, so they drop into the shard_map step unchanged
(the gathered feature matrix is the all-gathered global one, mirroring
the reference's whole-region input requirement,
``scattergather.cc:70-72``).  Which layout wins where is a chip
measurement: ``PERF.md`` §5–§6 and the ledger hold the current ones,
``BASELINE.md`` the July leads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def aggregate_segment(feats: jax.Array, edge_src: jax.Array,
                      edge_dst: jax.Array, num_rows: int) -> jax.Array:
    """Reference implementation: out[d] = sum over edges of feats[src].

    feats: [V(+1), F] source features (last row may be the zero dummy row).
    edge_src/edge_dst: int32 [E].  Returns [num_rows, F].
    """
    gathered = feats[edge_src]
    return jax.ops.segment_sum(gathered, edge_dst, num_segments=num_rows)


def aggregate_ell(feats: jax.Array, ell_idx, ell_row_pos: jax.Array,
                  num_rows: int,
                  budget_elems: int = 1 << 24,
                  ell_w=None) -> jax.Array:
    """Degree-bucketed ELLPACK aggregation (see core/ell.py): per width
    bucket, gather ``feats[idx]`` and sum the width axis; inverse-permute
    the concatenated bucket outputs back to row order.  No scatter, no
    per-edge scan — the TPU-native layout for the reference's CSR hot
    loop (``scattergather_kernel.cu:20-76``).

    feats: [R+1, F] gathered features with trailing zero row.
    ell_idx: tuple of int32 [rows_b, width_b] arrays (dummy = R).
    ell_row_pos: int32 [num_rows] output permutation (zero slot = total
    bucket rows).  Buckets whose gathered block would exceed
    ``budget_elems`` scalars (R * W * F, i.e. bytes/4 in fp32 — default
    64 MiB) are processed in row segments with lax.scan to bound the
    transient.

    ``ell_w`` (optional): per-bucket edge weights shaped like
    ``ell_idx`` (core/ell.py ell_weight_tables — the baked
    ``D^-1/2 A D^-1/2`` scales of the fused aggregation); the gathered
    rows are weighted in-register before the width reduction, so the
    weighted sum costs no extra HBM pass over the features.
    """
    F = feats.shape[1]
    outs = []
    for bi, idx in enumerate(ell_idx):
        w = (ell_w[bi].astype(feats.dtype)
             if ell_w is not None and len(ell_w) else None)
        R, W = idx.shape
        if R * W * F <= budget_elems:
            g = feats[idx]
            if w is not None:
                g = g * w[:, :, None]
            outs.append(g.sum(axis=1))
            continue
        segs = -(-R * W * F // budget_elems)
        seg_rows = -(-R // segs)
        Rp = seg_rows * segs
        pad = jnp.full((Rp - R, W), feats.shape[0] - 1, dtype=idx.dtype)
        idx_p = jnp.concatenate([idx, pad], axis=0)
        xs = (idx_p.reshape(segs, seg_rows, W),)
        if w is not None:
            w_p = jnp.concatenate(
                [w, jnp.zeros((Rp - R, W), dtype=w.dtype)], axis=0)
            xs += (w_p.reshape(segs, seg_rows, W),)

        def body(_, ch):
            g = feats[ch[0]]
            if len(ch) > 1:
                g = g * ch[1][:, :, None]
            return None, g.sum(axis=1)

        _, segs_out = lax.scan(body, None, xs)
        outs.append(segs_out.reshape(Rp, F)[:R])
    zero = jnp.zeros((1, F), dtype=feats.dtype)
    cat = jnp.concatenate(outs + [zero], axis=0)
    return cat[ell_row_pos]


def scan_window_rows(win_rows: int, carry_rows: int) -> int:
    """Rows of the carry a chunk step of :func:`_scan_window_sum`
    reads and writes, given the table's ``win_rows`` (0: none known).
    The window costs three passes over its rows (slice, scatter,
    write-back) against one scatter over the whole carry, and a tall
    one no longer fits VMEM: on the v5e it wins up to half the carry's
    height and loses past it (PERF §6, PR 27: -8% a chunk step at a
    tenth, -4% at a third, -0.4% at half, +2.4% at 0.6-0.9), so past
    half the step takes the whole carry."""
    return win_rows if 0 < 2 * win_rows <= carry_rows else carry_rows


def _scan_window_sum(out: jax.Array, table: jax.Array, xs,
                     win_rows: int, slot_major: bool = False
                     ) -> jax.Array:
    """The chunk scan of the width-8 sub-row layouts — one body for
    :func:`aggregate_ell_sect` (per section) and
    :func:`aggregate_flat_sum` (its single global section).  Each step
    gather-sums a chunk's ``[seg_rows, 8]`` ids out of ``table`` and
    adds the ``[seg_rows, F]`` partials into the carry ``out``.

    The destinations of a chunk are ascending, so its real ones lie
    in one run of at most ``win_rows`` rows (core/ell.py
    ``chunk_window_rows``).  The step therefore slices that window out
    of the carry at the chunk's first destination, scatter-adds into
    the window, and writes it back: XLA updates a scan carry in place
    under ``dynamic_update_slice``, so a step reads and writes
    ``win_rows`` rows instead of the whole ``[num_rows + 1, F]``
    carry.  Chunk padding (destination ``num_rows``; partial exactly
    zero: dummy source row, weight 0) is clamped onto the window's
    last row, where adding a zero changes nothing.  Where
    :func:`scan_window_rows` says the window does not pay (none known,
    or taller than half the carry) the window IS the carry, and XLA
    folds the slice and the write-back away: the whole-carry scatter.

    The body is width-agnostic and runs at whatever ``F`` its operands
    have.  ``flat_sum`` through ``GraphContext`` never has one under
    the 128 lanes: a narrower sum is zero-padded on its feature axis
    first (``core/ell.py agg_lane_width``), because XLA stores a
    whole ``[rows, F < 128]`` table in HBM with the vertex axis minor
    and a row gather then touches ``F`` tiles; a section block staged
    in VMEM is lane-padded by XLA itself, so ``sectioned`` keeps the
    model's width (PERF §6, PR 32).

    xs: ``(idx [n, seg, 8], dst [n, seg])`` plus optional weights
    shaped like ``idx``, in the table's dtype or wider.

    ``slot_major``: ``idx`` (and the weights) arrive ``[n, 8 * seg]``,
    a chunk's ``[8, seg]`` transpose flattened, and a step reshapes
    and transposes its own chunk back before the gather — the same
    step on the same values.  What it changes is where the tables
    live between steps: the TPU tiles an array's two minor axes ``(8,
    128)``, so the scan's operand ``[n, seg, 8]`` is held with its
    8-wide axis padded to 128 lanes, sixteen times its bytes (3.5 GiB
    for a 58M-slot int32 table, and as much again for its weights; a
    ``[n, 8, seg]`` operand is no better: XLA's layout assignment puts
    the 8-wide axis minor again), while ``[n, 8 * seg]`` has no
    narrow axis to pad and only the 256 KiB chunk in flight is."""
    carry_rows, F = out.shape
    win = scan_window_rows(win_rows, carry_rows)

    def body(o, ch):
        idx_ch, dst_ch = ch[0], ch[1]
        if slot_major:
            idx_ch = idx_ch.reshape(8, -1).T
        g = table[idx_ch]
        if len(ch) > 2:
            g = g * (ch[2].reshape(8, -1).T if slot_major
                     else ch[2])[:, :, None]
        part = g.sum(axis=1)
        if part.dtype != o.dtype:
            # fp32 weights over a narrower table (the relation means,
            # ``weights_fp32``): the products and the width-8 sum are
            # fp32, the partial is rounded once on its way to the carry
            part = part.astype(o.dtype)
        # clamped so the slice never clips (an all-padding chunk, or a
        # run that ends at the carry's last rows)
        r0 = jnp.minimum(dst_ch[0], carry_rows - win)
        w = lax.dynamic_slice(o, (r0, 0), (win, F))
        w = w.at[jnp.minimum(dst_ch - r0, win - 1)].add(
            part, indices_are_sorted=True)
        return lax.dynamic_update_slice(o, w, (r0, 0)), None

    return lax.scan(body, out, xs)[0]


def aggregate_ell_sect(feats: jax.Array, sect_idx, sect_sub_dst,
                       sect_meta, num_rows: int,
                       sect_w=None) -> jax.Array:
    """Source-sectioned width-8 aggregation (core/ell.py SectionedEll —
    the measured numbers and the why live on that dataclass).  Per
    section: slice the <= 64 MiB source block out of ``feats`` (XLA
    keeps it VMEM-resident), ``lax.scan`` over sub-row chunks carrying
    the output — gather-sum ``xsec[idx].sum(1)`` hits the fast gather
    path, then a sorted scatter-add of the ``[seg_rows, F]`` partials
    into the chunk's destination window (:func:`_scan_window_sum`).

    feats: [src_rows(+ optional trailing rows), F]; sections read
      ``[start, start+size)`` so an appended global dummy row is fine.
      ``F`` is the model's own width, narrow or not: the section
      block is lane-padded in VMEM by XLA, and padding ``F`` by hand
      measured no gain (``core/ell.py agg_lane_width``).
    sect_idx / sect_sub_dst: SectionedEll.idx / .sub_dst as jax arrays.
    sect_meta: static tuple of (start, size, win_rows) per section
      (SectionedEll.meta); a bare (start, size) scans with the whole
      carry as its window.
    sect_w (optional): per-section edge weights shaped like
      ``sect_idx`` (SectionedEll.weight_tables — the baked fused-norm
      scales), applied in-register before the width reduction.
    """
    F = feats.shape[1]
    out = jnp.zeros((num_rows + 1, F), dtype=feats.dtype)
    zero = jnp.zeros((1, F), dtype=feats.dtype)
    weighted = sect_w is not None and len(sect_w) > 0
    for si, ((st, sz, *win), tbl, sdst) in enumerate(
            zip(sect_meta, sect_idx, sect_sub_dst)):
        xsec = jnp.concatenate(
            [lax.slice(feats, (st, 0), (st + sz, F)), zero], axis=0)
        xs = (tbl, sdst)
        if weighted:
            xs += (sect_w[si].astype(feats.dtype),)
        out = _scan_window_sum(out, xsec, xs, win[0] if win else 0)
    return out[:num_rows]


def aggregate_flat_sum(feats: jax.Array, flat_idx: jax.Array,
                       flat_dst: jax.Array, num_rows: int,
                       flat_w=None, win_rows: int = 0,
                       weights_fp32: bool = False,
                       slot_major: bool = False) -> jax.Array:
    """Uniform width-8 sub-row SUM — the sum-path twin of the
    attention layout's ``gat_aggregate_flat8`` (ops/attention.py) and
    the compile-wall fix for the per-bucket ELL unroll: every row's
    neighborhood is split into width-8 sub-rows in ONE
    ``[n_chunks, seg_rows, 8]`` table (core/ell.py
    ``flat_sum_from_graph`` — a :class:`SectionedEll` with a single
    section spanning all sources, so ids are global/gathered
    coordinates), and the aggregation is ONE ``lax.scan`` whose body
    shape depends only on (dtype, seg_rows, F) — never on the degree
    distribution.  ``aggregate_ell``'s per-width Python unroll
    compiles one gather+reduce program per degree bucket (doubled by
    autodiff); this path compiles exactly one scan program per
    (dtype, F-quantum), which is what lets the persistent compile
    cache and the prewarm pass (utils/prewarm.py) cover large graphs.

    feats: [G+1, F] gathered features with trailing zero row (== the
      dummy id in ``flat_idx``).  ``GraphContext`` hands in ``F >=
      128``: a narrower sum arrives zero-padded (``core/ell.py
      agg_lane_width``), so the whole table is row-major in HBM and a
      gathered row is one tile line; a direct call at any ``F`` gives
      the same real columns, bit for bit.
    flat_idx: int32 [n_chunks, seg_rows, 8]; flat_dst: int32
      [n_chunks, seg_rows] output rows, ascending within each chunk
      (chunk padding points at ``num_rows``).
    flat_w (optional): fp32 shaped like ``flat_idx`` — the baked
      ``D^-1/2 A D^-1/2`` fused-normalization entries
      (``SectionedEll.weight_tables`` of the single section), applied
      in-register before the width reduction.
    win_rows: static height of a chunk's destination window
      (``SectionedEll.win_rows[0]``; 0 = the whole carry) — the scan
      is :func:`_scan_window_sum`, shared with the sectioned layout.
    weights_fp32: keep ``flat_w`` in fp32 instead of rounding it to the
      table's dtype — the relation means' ``1 / deg`` (bfloat16 would
      put up to 0.4% of gain error on a whole row; the baked
      ``D^-1/2 A D^-1/2`` entries of the fused path stay as they
      were).  ``feats`` and ``num_rows`` may be different index
      spaces: the table's ids index ``feats``, ``flat_dst`` the
      output.
    slot_major: ``flat_idx`` / ``flat_w`` are ``[n_chunks, 8 *
      seg_rows]`` (:func:`_scan_window_sum`): the relation tables'
      form, unpadded at rest.
    """
    F = feats.shape[1]
    out = jnp.zeros((num_rows + 1, F), dtype=feats.dtype)
    xs = (flat_idx, flat_dst)
    if flat_w is not None:
        xs += (flat_w if weights_fp32 else flat_w.astype(feats.dtype),)
    return _scan_window_sum(out, feats, xs, win_rows,
                            slot_major)[:num_rows]


def aggregate_flat_max(feats: jax.Array, flat_idx: jax.Array,
                       flat_dst: jax.Array, num_rows: int) -> jax.Array:
    """Neighbor MAX over the uniform width-8 layout (MIN via negation
    at the call site) — one scan program like
    :func:`aggregate_flat_sum`, with the width reduction a masked max
    and the per-chunk combine a sorted scatter-max (max is
    associative, so a row's sub-rows spanning chunks combine
    exactly).  Dummy/padding sources weigh -inf; rows with no real
    neighbor yield -inf here and the caller maps non-finite rows to 0
    (the sum path's empty-row convention, models/builder.py
    ``_max_fwd``)."""
    F = feats.shape[1]
    dummy = feats.shape[0] - 1
    neg = jnp.asarray(-jnp.inf, dtype=feats.dtype)
    out = jnp.full((num_rows + 1, F), neg, dtype=feats.dtype)

    def body(o, ch):
        idx_ch, dst_ch = ch
        g = feats[idx_ch]
        m = (idx_ch != dummy)[:, :, None]
        part = jnp.max(jnp.where(m, g, neg), axis=1)
        return o.at[dst_ch].max(part, indices_are_sorted=True), None

    out, _ = lax.scan(body, out, (flat_idx, flat_dst))
    return out[:num_rows]


def aggregate_ell_max(feats: jax.Array, ell_idx, ell_row_pos: jax.Array,
                      num_rows: int,
                      budget_elems: int = 1 << 24) -> jax.Array:
    """ELL neighbor MAX (MIN via negation at the call site): per
    bucket, gather and max over the width axis with dummy/padding
    sources masked to -inf.  Large buckets are row-segmented with
    ``lax.scan`` under the same ``budget_elems`` transient bound as
    :func:`aggregate_ell` — a mid-width bucket x wide F must not
    materialize past the budget on the MAX path either (ADVICE r2 /
    VERDICT r2 weak #5).  Rows with no real neighbor yield -inf here;
    the caller maps non-finite rows to 0 (matching the sum path's
    empty-row convention)."""
    F = feats.shape[1]
    dummy = feats.shape[0] - 1
    neg = jnp.asarray(-jnp.inf, dtype=feats.dtype)

    def seg_max(idx_seg):
        g = feats[idx_seg]                           # [r, W, F]
        m = (idx_seg != dummy)[:, :, None]
        return jnp.max(jnp.where(m, g, neg), axis=1)

    outs = []
    for idx in ell_idx:
        R, W = idx.shape
        if R * W * F <= budget_elems:
            outs.append(seg_max(idx))
            continue
        segs = -(-R * W * F // budget_elems)
        seg_rows = -(-R // segs)
        Rp = seg_rows * segs
        pad = jnp.full((Rp - R, W), dummy, dtype=idx.dtype)
        idx_p = jnp.concatenate([idx, pad], axis=0)

        def body(_, ch):
            return None, seg_max(ch)

        _, segs_out = lax.scan(body, None,
                               idx_p.reshape(segs, seg_rows, W))
        outs.append(segs_out.reshape(Rp, F)[:R])
    tail = jnp.full((1, F), neg, dtype=feats.dtype)
    cat = jnp.concatenate(outs + [tail], axis=0)
    return cat[ell_row_pos]

"""Neighbor aggregation (the reference's ScatterGather op).

Reference semantics (``scattergather_kernel.cu:20-76``): for a dst-major
CSR, ``out[dst] = sum_{(src,dst) in E} in[src]`` — a CSR-SpMM with an
implicit all-ones sparse matrix.  The reference backward *reuses the
forward kernel* on the same CSR (``scattergather_kernel.cu:160-170``),
which is correct only for symmetric adjacency; we get the exact transpose
for free from JAX autodiff (gather/segment_sum differentiate to the
scatter/gather pair), so our gradients are correct for any graph while
matching the reference bit-for-bit on the symmetric graphs it supports.

Three implementations, one semantics:

- ``segment``: one-shot gather + ``segment_sum``.  Materializes the
  ``[E, F]`` per-edge feature matrix — fine for small graphs and as the
  numerics reference for tests.
- ``blocked``: ``lax.scan`` over edge chunks.  Exploits dst-sortedness:
  because every vertex has a self edge (degree >= 1), the destinations
  inside a chunk of C edges span at most C consecutive rows, so each
  chunk reduces into a C-row window that is added back with a
  dynamic-slice read-modify-write.  The within-chunk reduction is a
  *one-hot selection matmul* (``onehot(dst-r0)^T @ gathered``) — entirely
  scatter-free, so it lands on the MXU instead of XLA's serialized TPU
  scatter path.  Memory is O(C * F) regardless of E.
- ``scan``: ``lax.scan`` over edge chunks with a *cumsum-diff* segmented
  reduction — the direct TPU analog of the reference's cub BlockScan
  kernel (``scattergather_kernel.cu:20-76``).  Within a chunk, row sums
  are prefix-sum differences at precomputed row-end offsets (O(C*F) VPU
  work instead of the one-hot matmul's O(C^2*F) MXU work), the chunk's
  last row travels as a carry record instead of a read-modify-write, and
  each window is *written exactly once* (later windows overwrite the
  provisional zero tail), so HBM traffic drops from 3x to 2x the gather
  bytes.  Carry records are scatter-added after the scan.  (On v5e the
  XLA row-gather dominates all impls — see benchmarks/micro_agg.py —
  so the practical default for big graphs is ``ell``, whose reduce is
  a dense reshape-sum.)
- ``pallas`` (kernels/ell_spmm.py): the ELL layout driven by a
  one-launch-per-bucket Pallas kernel — scalar-readable index blocks in
  SMEM, per-row feature DMA HBM->VMEM with a rotating pipeline, fp32
  VMEM accumulation; dispatched via GraphContext (needs the ELL tables,
  not an edge list).
- ``pallas_csr`` (kernels/spmm.py): the ``scan`` algorithm with the
  per-chunk segmented reduction fused into a Pallas TPU kernel
  (superseded by ``pallas``; kept as the edge-list-contract kernel).

All take per-edge *global* source ids and produce rows for the local
destination range, so they drop into the shard_map step unchanged (the
gathered feature matrix is the all-gathered global one, mirroring the
reference's whole-region input requirement, ``scattergather.cc:70-72``).

**Measured (TPU v5 lite, 2026-07-29, V=50k E=10M F=256 fp32, median of
10; benchmarks/measured_baselines.json has the full rows):** ``ell``
119.1 ms / 86.0 GB/s, ``sectioned`` 131.1 ms, ``scan:4096`` 260.0 ms,
``blocked:1024`` 294.6 ms, Pallas ELL kernel 1006.2 ms — each including
~66 ms constant fetch-barrier overhead.  At REDDIT scale (V=233k,
E=115M — gather table past VMEM) the ranking flips: ``sectioned``
865 ms vs ``ell`` 2006 ms per aggregation, 2708 vs 7920.8 ms per train
epoch (core/ell.py SectionedEll explains the mechanism).  The ``auto``
default picks by table size.
"""

from __future__ import annotations

import functools

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


@functools.partial(jax.jit, static_argnames=("num_rows", "chunk"))
def aggregate_blocked(feats: jax.Array, edge_src: jax.Array,
                      edge_dst: jax.Array, num_rows: int,
                      chunk: int = 512) -> jax.Array:
    """Chunked CSR aggregation with O(chunk * F) working set.

    Requires edge_dst sorted ascending and every destination row to have
    degree >= 1 over the *full* edge list (self-edge convention,
    ``gnn.cc:756``), which bounds the dst span of any chunk of C edges by
    C rows.  Padding edges must point at a zero source row and the last
    local row (partition.py guarantees both).
    """
    E = edge_src.shape[0]
    F = feats.shape[1]
    assert E % chunk == 0, "pad edges to a chunk multiple"
    n_chunks = E // chunk
    src_c = edge_src.reshape(n_chunks, chunk)
    dst_c = edge_dst.reshape(n_chunks, chunk)
    # Output padded by one window so the dynamic slice never clips.
    out0 = jnp.zeros((num_rows + chunk, F), dtype=feats.dtype)

    def body(out, inputs):
        src, dst = inputs
        r0 = dst[0]
        gathered = feats[src]                       # [C, F]
        local = dst - r0                            # in [0, C)
        # scatter-free segment reduction: sel[e, r] = (local[e] == r);
        # sel^T @ gathered lands on the MXU (fp32 accumulation)
        sel = (local[:, None] ==
               lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
               ).astype(gathered.dtype)
        prec = (lax.Precision.HIGHEST
                if gathered.dtype == jnp.float32 else None)
        seg = lax.dot_general(
            sel, gathered, (((0,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32).astype(out.dtype)
        window = lax.dynamic_slice(out, (r0, 0), (chunk, F))
        out = lax.dynamic_update_slice(out, window + seg, (r0, 0))
        return out, None

    out, _ = lax.scan(body, out0, (src_c, dst_c))
    return out[:num_rows]


@functools.partial(jax.jit, static_argnames=("num_rows", "chunk"))
def aggregate_scan(feats: jax.Array, edge_src: jax.Array,
                   edge_dst: jax.Array, num_rows: int,
                   chunk: int = 1024) -> jax.Array:
    """Cumsum-diff segmented reduction — the TPU BlockScan analog.

    Same preconditions as :func:`aggregate_blocked` (dst sorted, degree
    >= 1 over the full edge list, padding to a chunk multiple).  Within
    each chunk of C edges the row sums are differences of the running
    prefix sum at per-row end offsets (O(C*F) VPU work); the chunk's
    last row is emitted as a (row, partial-sum) carry record instead of
    read-modify-writing the output window, and each window is written
    exactly once — rows past the chunk's last destination are written as
    provisional zeros that the next window overwrites.  Carry records
    are scatter-added after the scan (duplicates accumulate, so a row
    spanning many chunks is summed exactly).
    """
    E = edge_src.shape[0]
    F = feats.shape[1]
    assert E % chunk == 0, "pad edges to a chunk multiple"
    C = chunk
    n_chunks = E // C
    src_c = edge_src.reshape(n_chunks, C)
    dst_c = edge_dst.reshape(n_chunks, C)
    # Output padded by one window so dynamic writes never clip.
    out0 = jnp.zeros((num_rows + C, F), dtype=feats.dtype)
    iota = lax.broadcasted_iota(jnp.int32, (C, 1), 0)

    def body(out, inputs):
        src, dst = inputs
        r0 = dst[0]
        pos = dst[C - 1] - r0                       # last row, local
        g = feats[src].astype(jnp.float32)          # [C, F] gather
        S1 = jnp.concatenate(
            [jnp.zeros((1, F), jnp.float32), jnp.cumsum(g, axis=0)])
        local = (dst - r0)[:, None]                 # [C, 1] in [0, C)
        # ends[j] = # edges with local dst <= j  (all dst >= r0 here)
        ends = jnp.sum((local <= iota.T).astype(jnp.int32), axis=0)
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), ends[:-1]])
        L = jnp.take(S1, ends, axis=0) - jnp.take(S1, starts, axis=0)
        carry = lax.dynamic_slice(L, (pos, 0), (1, F))
        L = jnp.where(iota == pos, 0.0, L).astype(out.dtype)
        out = lax.dynamic_update_slice(out, L, (r0, 0))
        return out, (dst[C - 1], carry[0].astype(out.dtype))

    out, (rows, vecs) = lax.scan(body, out0, (src_c, dst_c))
    out = out.at[rows].add(vecs)
    return out[:num_rows]


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
                     win_rows: int) -> jax.Array:
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

    xs: ``(idx [n, seg, 8], dst [n, seg])`` plus optional weights
    shaped like ``idx``."""
    carry_rows, F = out.shape
    win = scan_window_rows(win_rows, carry_rows)

    def body(o, ch):
        idx_ch, dst_ch = ch[0], ch[1]
        g = table[idx_ch]
        if len(ch) > 2:
            g = g * ch[2][:, :, None]
        part = g.sum(axis=1)
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


def aggregate_ell_sect_split(feats: jax.Array, sect_idx, sect_sub_dst,
                             sect_meta, num_rows: int) -> jax.Array:
    """:func:`aggregate_ell_sect` with the ``[N, W]`` block gather
    replaced by W independent ``[N]``-index row gathers summed as they
    go — a deliberately different XLA gather lowering raced against
    the block form in benchmarks/micro_agg.py (the block gather
    materializes the ``[N, W, F]`` transient before its width
    reduction; the split form keeps a single ``[N, F]`` accumulator)."""
    F = feats.shape[1]
    out = jnp.zeros((num_rows + 1, F), dtype=feats.dtype)
    zero = jnp.zeros((1, F), dtype=feats.dtype)
    for (st, sz, *_), tbl, sdst in zip(sect_meta, sect_idx,
                                       sect_sub_dst):
        xsec = jnp.concatenate(
            [lax.slice(feats, (st, 0), (st + sz, F)), zero], axis=0)
        W = tbl.shape[-1]

        def body(o, ch, xsec=xsec, W=W):
            idx_ch, dst_ch = ch
            part = xsec[idx_ch[:, 0]]
            for j in range(1, W):
                part = part + xsec[idx_ch[:, j]]
            return o.at[dst_ch].add(part, indices_are_sorted=True), None

        out, _ = lax.scan(body, out, (tbl, sdst))
    return out[:num_rows]


def aggregate_flat_sum(feats: jax.Array, flat_idx: jax.Array,
                       flat_dst: jax.Array, num_rows: int,
                       flat_w=None, win_rows: int = 0) -> jax.Array:
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
      dummy id in ``flat_idx``).
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
    """
    F = feats.shape[1]
    out = jnp.zeros((num_rows + 1, F), dtype=feats.dtype)
    xs = (flat_idx, flat_dst)
    if flat_w is not None:
        xs += (flat_w.astype(feats.dtype),)
    return _scan_window_sum(out, feats, xs, win_rows)[:num_rows]


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


def aggregate(feats: jax.Array, edge_src: jax.Array, edge_dst: jax.Array,
              num_rows: int, impl: str = "segment",
              chunk: int = 512) -> jax.Array:
    """Dispatch over implementations; identical numerics (fp32 addition
    order differs between impls — tests use tolerances accordingly)."""
    if impl == "segment":
        return aggregate_segment(feats, edge_src, edge_dst, num_rows)
    if impl == "blocked":
        return aggregate_blocked(feats, edge_src, edge_dst, num_rows,
                                 chunk=chunk)
    if impl == "scan":
        return aggregate_scan(feats, edge_src, edge_dst, num_rows,
                              chunk=chunk)
    if impl == "pallas":
        raise ValueError(
            "impl='pallas' is the one-launch ELL kernel "
            "(kernels/ell_spmm.py) and needs the ELL tables, not an "
            "edge list — route through GraphContext (aggr_impl='pallas') "
            "or call ell_aggregate_pallas directly")
    if impl == "pallas_csr":
        try:
            from ..kernels.spmm import csr_spmm_pallas
        except ImportError as e:
            raise NotImplementedError(
                "the pallas_csr aggregation kernel is not available in "
                "this build; use impl='blocked'") from e
        return csr_spmm_pallas(feats, edge_src, edge_dst, num_rows,
                               chunk=chunk)
    raise ValueError(f"unknown aggregate impl: {impl}")


def aggregate_mean(feats: jax.Array, edge_src: jax.Array,
                   edge_dst: jax.Array, num_rows: int,
                   in_degree: jax.Array, impl: str = "segment",
                   chunk: int = 512) -> jax.Array:
    """Mean aggregator (AGGR_AVG of the reference's declared-but-unbuilt
    AggrType enum, ``gnn.h:75-80``): sum / real in-degree."""
    s = aggregate(feats, edge_src, edge_dst, num_rows, impl=impl,
                  chunk=chunk)
    deg = jnp.maximum(in_degree.astype(s.dtype), 1.0)
    return s / deg[:, None]

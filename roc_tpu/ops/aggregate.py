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
  section; a table that fits VMEM has a sub-row's slots summed there
  by a Pallas kernel (:func:`_gather_sum`, :func:`gather_sum_form`),
  and where the table is dense the scan sums a row's sub-rows on
  the MXU before the carry sees them (:func:`scan_seg_sum`).
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

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_derivatives import SymbolicZero
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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


# What a chunk step's ways of adding ``seg_rows`` sorted partials into
# its window cost on the v5e, bfloat16, 256 lanes (my chip runs, PR 39,
# ``chiprun_out/pr39/mb1.json`` and ``mb2.json``: one jitted scan over
# a Reddit section at the cell's shapes — 32 chunks of 131,072
# sub-rows, windows 7,680 and 13,440 — each tile of 512 ... 4,096
# under each residual form; PERF §6, PR 39 has the table):
# - the serial window scatter, one sorted read-modify-write a sub-row:
#   1.4311 ms a step, 10.9 ns an update at any width (1.17 ms at 41
#   lanes, a sixth of the bytes; 13.1 at a products chunk of 8,192);
# - the banded one-hot product and the residual — ``nt * B`` float32
#   slab rows added into a float32 copy of the window by the row
#   scatter (its indices overlap, so they are not sorted): a least-
#   squares fit over the five measured (tile, band) points, 0.2562 -
#   0.3924 ms a step, reads 169 TFLOP/s of the chip's 197 and 10.8 ns
#   a slab row, to within 2 us, beside 0.05 - 0.08 ms that does not
#   scale (the window's slice, conversion and write-back).
# Of the residual forms raced at Reddit's shapes (tiles 512 ... 4,096,
# ms a step): the row scatter 0.26 - 0.28 with float32 slabs (0.28 -
# 0.30 with slabs rounded to bfloat16 first, and worse rows);
# ``lax.scatter_add`` with ``[B, F]`` update windows 0.51 at 1,024 and
# 0.30 at 4,096; an inner loop of ``dynamic_slice`` + add +
# ``dynamic_update_slice`` 0.60 at 512 down to 0.27 at 4,096 (2-5 us
# a slab).  The row scatter in float32 is kept.
SEG_SUM_UPDATE_NS = 10.9
SEG_SUM_SLAB_ROW_NS = 10.8
SEG_SUM_TFLOPS = 169.0
# engage only where the model says the step's combine costs under
# three quarters of the scatter's: the model is two slopes, not the
# chip (it leaves out what does not scale, and reads a products chunk
# at half its measured time)
SEG_SUM_MARGIN = 0.75


def scan_seg_sum(seg_rows: int, win_rows: int, bands, F: int):
    """``(T, B)`` — the tile height and band the chunk step's
    segmented sum runs at (:func:`_seg_sum`) — or None where the
    serial scatter stays.  Derived, no knob: ``bands`` is the table's
    own (``core/ell.py chunk_bands``: per tile height ``T`` dividing
    ``seg_rows``, the most destination rows ``T`` consecutive sub-rows
    span), ``win_rows`` the window the step runs in, ``F`` the width.

    A tile's ``T`` partials become ``B`` slab rows on the MXU, so the
    step adds ``seg_rows / T * B`` rows where it added ``seg_rows``,
    and pays ``2 * seg_rows * B * F`` FLOP for it (``F`` in whole
    128-lane registers).  The tile with the least modelled time wins
    (the constants above), and the rule engages only where that is
    well under the scatter's.  It therefore reads the table: Reddit's
    sections (10-17.7 sub-rows a destination row; a tile of 512 spans
    58-88 rows) engage at a tenth to an eighth of their 131,072 rows a
    step; a products partition (6.9 a row) at 8 x 224 for 8,192; ogbn-arxiv
    (1.1-1.4 a row: a tile of 1,024 spans 988 rows) and the typed
    graph's stacked relation rows (a tile spans more rows than it has
    sub-rows) have nothing to reduce and keep the scatter.  A band
    taller than the window has no slab to place and is skipped."""
    lanes = -(-F // 128) * 128
    best = None
    for T, B in bands:
        if seg_rows % T or B > win_rows:
            continue
        ns = (seg_rows // T * B * SEG_SUM_SLAB_ROW_NS
              + 2 * seg_rows * B * lanes / (SEG_SUM_TFLOPS * 1e3))
        if best is None or ns < best[0]:
            best = (ns, T, B)
    if best is None or best[0] >= (SEG_SUM_MARGIN * seg_rows
                                   * SEG_SUM_UPDATE_NS):
        return None
    return best[1:]


def seg_sum_updates(n_chunks: int, seg_rows: int, seg) -> list:
    """``[before, after]``: rows a pass over ``n_chunks`` chunks adds
    into the carry's window one by one without and with the
    segmented sum ``seg`` (:func:`scan_seg_sum`'s ``(T, B)`` or
    None) — the ``plan`` line's ``agg_carry_updates``."""
    before = n_chunks * seg_rows
    return [before, before if seg is None
            else n_chunks * (seg_rows // seg[0]) * seg[1]]


def _seg_sum(w: jax.Array, part: jax.Array, loc: jax.Array,
             T: int, B: int) -> jax.Array:
    """``w.at[loc].add(part)`` for ascending ``loc`` with a row's
    sub-rows summed on the MXU first: the ``[S, F]`` partials are cut
    into ``S / T`` tiles; a tile's destinations lie within ``B`` rows
    of its first (the table's band), so a ``[B, T]`` one-hot of
    ``loc - base`` times the tile's ``[T, F]`` partials is its
    ``[B, F]`` slab of per-row sums, accumulated in float32.  The
    one-hot is exact in any dtype; a float32 ``part`` multiplies at
    ``Precision.HIGHEST`` (the fp32 runs and the tests' ``segment``
    reference), a bfloat16 one in the single default pass (0/1 x
    bf16 is exact).  The slabs are added at rows ``base + [0, B)`` by
    the row scatter — slabs overlap where a row's sub-rows cross a
    tile boundary, so this is an accumulation of ``S / T * B`` rows,
    not a placement — still in float32, into a float32 copy of the
    window, which is rounded to the carry's dtype once: a destination
    row takes one rounding a chunk.  That is what the serial scatter
    takes on the chip too (XLA:TPU adds a bfloat16 scatter's updates
    in float32 and rounds the window at the end; rounding the slabs
    first read 10% worse rows at Reddit, PERF §6, PR 39), and fewer
    than XLA:CPU's one a sub-row.

    ``base`` is clamped so a slab never leaves the window.  Chunk
    padding (``loc`` clamped to the window's last row, partial exactly
    zero) falls inside a tile's band only where the slab ends at that
    row, and adds its zero there; anywhere else its one-hot column is
    empty and it is dropped."""
    win, F = w.shape
    nt = part.shape[0] // T
    loc = loc.reshape(nt, T)
    base = jnp.minimum(loc[:, 0], win - B)
    band = jnp.arange(B, dtype=loc.dtype)
    sel = ((loc - base[:, None])[:, None, :]
           == band[None, :, None]).astype(part.dtype)
    slab = jnp.einsum(
        "tbj,tjf->tbf", sel, part.reshape(nt, T, F),
        preferred_element_type=jnp.float32,
        precision=(lax.Precision.HIGHEST
                   if part.dtype == jnp.float32 else None))
    rows = (base[:, None] + band[None, :]).reshape(nt * B)
    return w.astype(jnp.float32).at[rows].add(
        slab.reshape(nt * B, F)).astype(w.dtype)


# What a chunk step's ``[seg_rows, F]`` partials cost on the v5e (my
# chip run, PR 41, ``chiprun_out/pr41/race.json``: one jitted scan a
# form at the cells' shapes, window and segmented sum as in the cells;
# ms a chunk step, the parent's gather + reduce first; PERF §6, PR 41):
# - a VMEM-sized table: Reddit's section (65,537 rows, 131,072
#   sub-rows, bf16 weights) 4.653 at 256 lanes and 3.301 at 41;
#   arxiv's (56,449 rows, 106,496 sub-rows) 5.512 at 256 weighted,
#   5.457 unweighted, 3.287 at 128.  The kernel below holding the
#   table in VMEM: 2.406 at Reddit's 256 lanes with two bfloat16
#   columns a 32-bit word (3.947 with the table widened to float32:
#   the kernel is bound by its row loads, one vreg a 128-lane word
#   row), 2.049 at 41, 5.07 / 4.61 / 2.41 at arxiv's with float32
#   words; tiles of 256 ... 1,024 sub-rows within 1%.  XLA's own
#   forms lose there: eight ``[seg, F]`` gathers summed 6.692 / 3.158,
#   one slot-major ``[8, seg, F]`` gather summed 7.693 / 4.277 —
#   XLA keeps the gather a fusion of its own in every form, so the
#   gathered rows round-trip through HBM whichever way they are summed.
#   In the Reddit cell the kernel takes 2.281 / 2.073 ms a chunk step
#   at 256 / 41 lanes, 2.18 / 1.98 ns a slot (the traced run, PERF §5).
# - a table in HBM (products' 2,449,030 rows, typed 1,939,744, chunks
#   of 8,192): 0.821 / 0.695 at products' 256 / 128 lanes, 0.719 typed
#   (fp32 weights, slot-major); slot-major gather + float32 sum over
#   the leading axis 0.751 / 0.666 / 0.686 — the eight-gather form
#   the same to 0.3%.  No kernel holds such a table.
# The rule: a table whose kernel needs no more VMEM than the most it
# was measured holding is summed by the kernel; any other is gathered
# slot-major and summed over the leading axis in float32.  Both round a
# partial once, from float32.  The kernel's VMEM is the table's words,
# its float32 accumulator and its two output blocks
# (:func:`_vmem_bytes`); the most measured is the float32 Reddit
# section at 256 lanes, 64.0 + 0.5 + 1.0 MiB, run under a limit 16 MiB
# above that.  The constant is the v5e's: 128 MiB of VMEM a core.  A
# chip with less needs its own (as ``core/ell.py`` keeps the section
# bound by TPU kind).
GATHER_SUM_VMEM_BYTES = 66 << 20
GATHER_SUM_TILE = 512            # sub-rows a grid step
LANES = 128


def _vmem_lanes(F: int, dtype) -> int:
    """32-bit lanes a row of a ``[rows, F]`` table of ``dtype`` takes in
    the kernel's VMEM: two bfloat16 columns a word past one vreg (in
    groups of 256 columns), one float32 word a column otherwise."""
    lanes = -(-F // LANES) * LANES
    if jnp.dtype(dtype) == jnp.bfloat16 and lanes > LANES:
        return -(-F // (2 * LANES)) * LANES
    return lanes


def _vmem_bytes(rows: int, F: int, dtype, tile: int = GATHER_SUM_TILE
                ) -> int:
    """VMEM the kernel holds for a ``[rows, F]`` table of ``dtype``
    (its partials in ``dtype`` too) at ``tile`` sub-rows a grid step:
    the table's words (rows padded to 8), the float32 accumulator
    ``[tile, cols]`` and the double-buffered ``[tile, F]`` output
    blocks, each row padded to whole 128-lane registers."""
    lanes = _vmem_lanes(F, dtype)
    padded = -(-F // LANES) * LANES
    cols = 2 * lanes if lanes != padded else lanes
    return (-(-rows // 8) * 8 * lanes * 4 + tile * cols * 4
            + 2 * tile * padded * jnp.dtype(dtype).itemsize)


def gather_sum_form(rows: int, F: int, dtype=jnp.bfloat16) -> str:
    """How a chunk step of :func:`_scan_window_sum` makes its partials
    out of a ``[rows, F]`` table of ``dtype``: ``"fused"`` — what the
    kernel holds fits its VMEM (:func:`_vmem_bytes` against
    :data:`GATHER_SUM_VMEM_BYTES`) and :func:`_gather_sum` sums a
    sub-row's 8 slots there, writing only ``[seg_rows, F]`` — or
    ``"two_pass"``: the gathered slots reach HBM and are read back by
    the sum.  Read off the shapes; no knob."""
    fits = _vmem_bytes(rows, F, dtype) <= GATHER_SUM_VMEM_BYTES
    return "fused" if fits else "two_pass"


def gather_sum_slots(n_chunks: int, seg_rows: int, rows: int, F: int,
                     dtype=jnp.bfloat16, width: int = 8) -> list:
    """``[form, slots]`` for a table of ``rows`` rows scanned in
    ``n_chunks`` chunks of ``seg_rows`` sub-rows of ``width`` slots at
    feature width ``F`` — the ``plan`` line's ``agg_gather_sum``."""
    return [gather_sum_form(rows, F, dtype),
            n_chunks * seg_rows * width]


def _vmem_words(table: jax.Array) -> jax.Array:
    """``table`` as the kernel holds it: float32 words, or — a
    bfloat16 table wider than 128 lanes — column ``256 g + j`` in the
    low half of word ``128 g + j`` and column ``256 g + 128 + j`` in
    its high half (zero-padded to whole groups), so a row is half the
    loads and unpacks into 128-lane-aligned pieces.  The rows are
    zero-padded to a multiple of 8, as VMEM holds them anyway: sections
    a row apart in size then share one compiled kernel (the last row
    stays a zero dummy)."""
    R, F = table.shape
    if R % 8:
        table = jnp.concatenate(
            [table, jnp.zeros((-R % 8, F), table.dtype)], axis=0)
        R += -R % 8
    L = _vmem_lanes(F, table.dtype)
    if L == -(-F // LANES) * LANES:
        return table.astype(jnp.float32)
    bits = lax.bitcast_convert_type(
        jnp.pad(table, ((0, 0), (0, 2 * L - F))), jnp.uint16)
    bits = bits.astype(jnp.uint32).reshape(R, L // LANES, 2, LANES)
    return (bits[:, :, 0] | (bits[:, :, 1] << 16)).reshape(R, L)


def _gather_sum_kernel(*refs, weighted: bool, packed: bool, F: int,
                       unroll: int):
    """One grid step: ``T`` sub-rows' partials.  The table's words are
    copied into VMEM once a call (the grid runs in order and the
    scratch persists); a sub-row's 8 ids (and weights) are read from
    SMEM, its 8 rows loaded one by one, each widened to float32 (a
    bfloat16 column is exact in float32, and so is its product with a
    bfloat16 weight) and summed in float32; the tile is rounded to the
    output's dtype once.  The ids (and weights) arrive slot-major,
    ``[8, T]``.  ``unroll`` sub-rows an iteration of the
    loop: 8 on the chip, 1 interpreted (a fifth of the compile time
    off the TPU, where the loop's speed does not matter)."""
    if weighted:
        idx_ref, w_ref, tab_hbm, out_ref, tab, acc = refs
    else:
        idx_ref, tab_hbm, out_ref, tab, acc = refs

    @pl.when(pl.program_id(0) == 0)
    def _():
        pltpu.sync_copy(tab_hbm, tab)

    groups = tab.shape[1] // LANES

    def slot(r, k):
        v = tab[pl.ds(idx_ref[k, r], 1), :]
        if packed:
            lo = lax.bitcast_convert_type(v << 16, jnp.float32)
            hi = lax.bitcast_convert_type(
                v & jnp.uint32(0xFFFF0000), jnp.float32)
            v = jnp.concatenate(
                [p[:, g * LANES:(g + 1) * LANES]
                 for g in range(groups) for p in (lo, hi)], axis=1)
        return v * w_ref[k, r] if weighted else v

    def row(u, q):
        r = q * unroll + u
        # a loop unrolled whole is traced once and lowered step by step
        # by Mosaic: the code Python's unrolling gave (PERF §6, PR 41),
        # two traces of a slot where that took 64 an iteration of rows
        acc[pl.ds(r, 1), :] = lax.fori_loop(
            1, idx_ref.shape[0], lambda k, s: s + slot(r, k),
            slot(r, 0), unroll=True)
        return q

    def rows(q, carry):
        lax.fori_loop(0, unroll, row, q, unroll=True)
        return carry

    lax.fori_loop(0, acc.shape[0] // unroll, rows, 0)
    out_ref[...] = acc[:, :F].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("F", "dtype", "interpret"))
def _gather_sum_call(words: jax.Array, idx: jax.Array, w, F: int,
                     dtype, interpret=None) -> jax.Array:
    """``[seg, F]`` partials of the slot-major ``[8, seg]`` ids ``idx``
    (weights ``w`` like it, or None) out of the table's VMEM ``words``
    (:func:`_vmem_words`) — the Pallas call; interpreted off the TPU.
    Slot-major because a ``[seg, 8]`` operand is held with its 8-wide
    axis padded to 128 lanes, and flattening it is a relayout that
    XLA:TPU takes 20 s to compile at 131,072 sub-rows; a ``[8, seg]``
    tile of ids is what SMEM holds as it is.  A chunk whose height the
    tile does not divide is padded with the table's last row (the zero
    dummy) at weight 0."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    width, seg = idx.shape
    idx = idx.astype(jnp.int32)     # SMEM holds 32-bit words
    R, L = words.shape
    T = min(GATHER_SUM_TILE, -(-seg // 128) * 128)
    pad = -(-seg // T) * T - seg
    if pad:
        idx = jnp.concatenate(
            [idx, jnp.full((width, pad), R - 1, idx.dtype)], axis=1)
        if w is not None:
            w = jnp.concatenate([w, jnp.zeros((width, pad), w.dtype)],
                                axis=1)
    smem = pl.BlockSpec((width, T), lambda i: (0, i),
                        memory_space=pltpu.SMEM)
    args, specs = [idx], [smem]
    if w is not None:
        args.append(w.astype(jnp.float32))
        specs.append(smem)
    packed = words.dtype == jnp.uint32
    cols = 2 * L if packed else L
    word_bytes = R * (-(-L // LANES) * LANES) * 4
    out = pl.pallas_call(
        functools.partial(_gather_sum_kernel, weighted=w is not None,
                          packed=packed, F=F, unroll=1 if interpret else 8),
        out_shape=jax.ShapeDtypeStruct((seg + pad, F), dtype),
        grid=((seg + pad) // T,),
        in_specs=specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((T, F), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((R, L), words.dtype),
                        pltpu.VMEM((T, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(R, F, dtype, T) + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * width * (seg + pad) * cols, transcendentals=0,
            bytes_accessed=word_bytes + (seg + pad) * (
                width * 4 * (2 if w is not None else 1)
                + F * jnp.dtype(dtype).itemsize)),
        name="agg_gather_sum",
        interpret=interpret,
    )(*args, words)
    return out[:seg] if pad else out


def _two_pass_sum(table, idx, w, dtype):
    """``sum_k table[idx[k]] (* w[k])`` over slot-major ``[8, seg]``
    ids and weights in XLA: one ``[8, seg, F]`` gather (it reaches
    HBM), each row times its weight in float32, summed over the slot
    axis in float32 and rounded once to ``dtype`` — the partials of a
    table the kernel cannot hold, and the kernel's tangent."""
    g = table[idx].astype(jnp.float32)
    if w is not None:
        g = g * w.astype(jnp.float32)[:, :, None]
    return g.sum(axis=0).astype(dtype)


@functools.partial(jax.custom_jvp, nondiff_argnums=(4,))
def _gather_sum(table, words, idx, w, dtype):
    """:func:`_two_pass_sum`'s value computed by the kernel from
    ``words``, the table's VMEM form (:func:`_vmem_words`, made once a
    scan and handed in under ``stop_gradient``).  Linear in ``table``:
    the tangent is :func:`_two_pass_sum` of the tangent, which JAX
    transposes into the scatter a directed graph's autodiff needs.  The
    weights carry no gradient: every caller's are constants (the baked
    norm scales, ``1/deg``, the softmax aggregation's detached ones)."""
    return _gather_sum_call(words, idx, w, table.shape[1], dtype)


def _gather_sum_jvp(dtype, primals, tangents):
    table, words, idx, w = primals
    dtable, _, _, dw = tangents
    if w is not None and not isinstance(dw, SymbolicZero):
        raise TypeError("the gather-sum kernel's weights carry no "
                        "gradient (ops/aggregate.py _gather_sum)")
    out = _gather_sum(table, words, idx, w, dtype)
    if isinstance(dtable, SymbolicZero):
        return out, jnp.zeros(out.shape, dtype)
    return out, _two_pass_sum(dtable, idx, w, dtype)


_gather_sum.defjvp(_gather_sum_jvp, symbolic_zeros=True)


def _scan_window_sum(out: jax.Array, table: jax.Array, xs,
                     win_rows: int = 0, bands=(),
                     slot_major: bool = False) -> jax.Array:
    """The chunk scan of the width-8 sub-row layouts — one body for
    :func:`aggregate_ell_sect` (per section) and
    :func:`aggregate_flat_sum` (its single global section).  Each step
    sums the rows of a chunk's ``[seg_rows, 8]`` ids out of ``table``
    into ``[seg_rows, F]`` partials and adds them into the carry
    ``out``.

    How the partials are made is read off the table
    (:func:`gather_sum_form`; nothing to configure).  A table whose
    words fit the kernel's VMEM — every section, any table at the
    tests' sizes — is summed by :func:`_gather_sum`: the table is
    copied into VMEM once a step (:func:`_vmem_words`: two bfloat16
    columns a 32-bit word past 128 lanes), a sub-row's 8 rows are
    loaded, widened and summed in float32 there, and only the
    partials, rounded once, reach HBM — no ``[seg_rows * 8, F]``
    gather written and read back, no separate reduce.  A table in HBM
    (products' flat table, the typed relation passes) is gathered
    slot-major, ``[8, seg_rows, F]``, and summed over that leading
    axis in float32, rounded once: the gathered rows still round-trip
    through HBM (no kernel holds the table), but the sum reads whole
    rows instead of reducing across sublanes.  The constants and the
    race they come from sit above :data:`GATHER_SUM_VMEM_BYTES`.

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

    Between the width-8 sum and the window sits the step's other
    decision (:func:`scan_seg_sum`, read off the table's ``bands``
    and ``F``; nothing to configure).  Where a destination row holds
    many sub-rows the partials are summed by row on the MXU first
    (:func:`_seg_sum`: a banded one-hot product a tile of sorted
    partials, float32 accumulation) and the window takes ``seg_rows /
    T * B`` slab rows instead of ``seg_rows`` serial updates — a
    tenth at Reddit.  The slabs and the window meet in float32, so a
    row is rounded to the carry's dtype once a chunk, and chunk
    padding either adds its zero to the window's last row as before
    or matches no row of a band and is dropped.  Where the rule
    returns None the body is the scatter's, token for token.

    The body is width-agnostic and runs at whatever ``F`` its operands
    have.  ``flat_sum`` through ``GraphContext`` never has one under
    the 128 lanes: a narrower sum is zero-padded on its feature axis
    first (``core/ell.py agg_lane_width``), because XLA stores a
    whole ``[rows, F < 128]`` table in HBM with the vertex axis minor
    and a row gather then touches ``F`` tiles; a section block staged
    in VMEM is lane-padded by XLA itself, so ``sectioned`` keeps the
    model's width (PERF §6, PR 32).

    xs: ``(idx [n, seg, 8], dst [n, seg])`` plus optional weights
    shaped like ``idx``, in the table's dtype or wider (a product is
    formed in float32 either way: a bfloat16 weight times a bfloat16
    column is exact there).  ``bands``:
    the section's ``SectionedEll.bands`` entry (``()``: none known,
    the scatter).

    ``slot_major``: ``idx`` (and the weights) arrive ``[n, 8 * seg]``,
    a chunk's ``[8, seg]`` transpose flattened, which is the order
    both forms read a chunk in (a ``[seg, 8]`` chunk is transposed by
    the step) — the same step on the same values.  What it changes is
    where the tables
    live between steps: the TPU tiles an array's two minor axes ``(8,
    128)``, so the scan's operand ``[n, seg, 8]`` is held with its
    8-wide axis padded to 128 lanes, sixteen times its bytes (3.5 GiB
    for a 58M-slot int32 table, and as much again for its weights; a
    ``[n, 8, seg]`` operand is no better: XLA's layout assignment puts
    the 8-wide axis minor again), while ``[n, 8 * seg]`` has no
    narrow axis to pad and only the 256 KiB chunk in flight is."""
    carry_rows, F = out.shape
    win = scan_window_rows(win_rows, carry_rows)
    seg = scan_seg_sum(xs[1].shape[-1], win, bands, F)
    fused = gather_sum_form(*table.shape, table.dtype) == "fused"
    if fused:
        words = lax.stop_gradient(_vmem_words(table))

    def body(o, ch):
        dst_ch = ch[1]
        # both forms read a chunk slot-major, [8, seg]
        idx8, w8 = [None if a is None else
                    a.reshape(8, -1) if slot_major else a.T
                    for a in (ch[0], ch[2] if len(ch) > 2 else None)]
        part = (_gather_sum(table, words, idx8, w8, o.dtype) if fused
                else _two_pass_sum(table, idx8, w8, o.dtype))
        # clamped so the slice never clips (an all-padding chunk, or a
        # run that ends at the carry's last rows)
        r0 = jnp.minimum(dst_ch[0], carry_rows - win)
        w = lax.dynamic_slice(o, (r0, 0), (win, F))
        if seg is None:
            w = w.at[jnp.minimum(dst_ch - r0, win - 1)].add(
                part, indices_are_sorted=True)
        else:
            w = _seg_sum(w, part, jnp.minimum(dst_ch - r0, win - 1),
                         *seg)
        return lax.dynamic_update_slice(o, w, (r0, 0)), None

    return lax.scan(body, out, xs)[0]


def aggregate_ell_sect(feats: jax.Array, sect_idx, sect_sub_dst,
                       sect_meta, num_rows: int,
                       sect_w=None) -> jax.Array:
    """Source-sectioned width-8 aggregation (core/ell.py SectionedEll —
    the measured numbers and the why live on that dataclass).  Per
    section: slice the <= 64 MiB source block out of ``feats``,
    ``lax.scan`` over sub-row chunks carrying the output — a chunk's
    ``[seg_rows, F]`` partials summed by the kernel that holds the
    section in VMEM (:func:`_gather_sum`: a sub-row's 8 rows in
    float32, nothing gathered written to HBM), then a sorted
    scatter-add of the partials into the chunk's destination window
    (:func:`_scan_window_sum`).

    feats: [src_rows(+ optional trailing rows), F]; sections read
      ``[start, start+size)`` so an appended global dummy row is fine.
      ``F`` is the model's own width, narrow or not: the section
      block is lane-padded in VMEM by XLA, and padding ``F`` by hand
      measured no gain (``core/ell.py agg_lane_width``).
    sect_idx / sect_sub_dst: SectionedEll.idx / .sub_dst as jax arrays.
    sect_meta: static tuple of (start, size, win_rows, bands) per
      section (SectionedEll.meta); a bare (start, size) scans with the
      whole carry as its window, and without ``bands`` the partials
      are scattered one by one.
    sect_w (optional): per-section edge weights shaped like
      ``sect_idx`` (SectionedEll.weight_tables — the baked fused-norm
      scales), each slot's row times its weight in float32 inside the
      kernel.
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
        out = _scan_window_sum(out, xsec, xs, *win)
    return out[:num_rows]


def aggregate_flat_sum(feats: jax.Array, flat_idx: jax.Array,
                       flat_dst: jax.Array, num_rows: int,
                       flat_w=None, win_rows: int = 0, bands=(),
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
    distribution.  Its partials (:func:`_scan_window_sum`): a table in
    HBM — any graph past the kernel's VMEM, products' 2.45M rows —
    is gathered slot-major and summed over the slot axis in float32;
    a table small enough for VMEM is summed by the kernel.
    ``aggregate_ell``'s per-width Python unroll compiles one
    gather+reduce program per degree bucket (doubled by autodiff);
    this path compiles exactly one scan program per
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
      (``SectionedEll.weight_tables`` of the single section), each
      slot's product formed in float32 before the width sum.
    win_rows: static height of a chunk's destination window
      (``SectionedEll.win_rows[0]``; 0 = the whole carry) — the scan
      is :func:`_scan_window_sum`, shared with the sectioned layout.
    bands: the table's tile bands (``SectionedEll.bands[0]``), from
      which the scan decides whether to sum a row's sub-rows on the
      MXU first (:func:`scan_seg_sum`); ``()`` keeps the scatter.
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
    return _scan_window_sum(out, feats, xs, win_rows, bands,
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

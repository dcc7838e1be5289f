"""Block-dense MXU aggregation: tiled-adjacency SpMM for community graphs.

The measured sectioned/ELL gather is ROW-RATE bound on v5e (~7 ns per
edge, width-insensitive below F=256 — BASELINE.md "where the epoch
goes"), i.e. the chip's gather unit, not HBM bytes, sets the 98%-of-
epoch aggregation cost.  The MXU escape hatch (VERDICT r4 #1): tile
the adjacency over the vertex id space into ``[128, 128]`` blocks and
aggregate every sufficiently-filled block as one bf16 batched matmul

    out[dst_tile] += A_tile @ x[src_tile]        (A_tile: [128, 128])

leaving the scattered residual edges to the sectioned gather.  Per
dense block the cost is pure bandwidth — A (uint8, cast on device) +
one source tile read + one fp32 output-tile update, ~0.2 us at F=256 —
so a block pays off past roughly

    fill* ~ 0.2us / 7ns ~ 30..64 edges per 128x128 block (<0.4% fill)

while a uniform-random graph at Reddit scale puts only
``E * 128^2 / V^2 ~ 35`` edges in a block (and spreads A over V^2/128^2
tiles, whose reads then dominate).  The path therefore targets graphs
with COMMUNITY structure exposed by the vertex order (real Reddit is
community-generated; ``core/reorder.py`` / the planted-community
generator's oracle order model the ordering quality) — ``plan_blocks``
reports the occupancy stats that decide it (no benchmark cell runs
it yet: ROADMAP R1).

Reference cost model being attacked: the one-thread-per-edge atomic
CSR kernel ``/root/reference/scattergather_kernel.cu:20-76``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK = 128          # MXU-native tile edge
_CHUNK_BLOCKS = 256  # blocks per scan step: bounds the [C,128,F] transient

# aggr_impl='auto' structure probe (probe_dense_frac): below this edge
# count the sectioned gather is cheap enough that planning overhead
# isn't worth probing; at/above this dense fraction the measured
# bdense win (1.64x at 0.52, 2.49x at 0.81 — BASELINE.md) justifies
# switching.  0.15 is conservative: every block past min_fill is
# already cheaper per edge than the 7 ns/edge gather, but a thin
# dense slice still costs A-table HBM residency next to the model.
BDENSE_AUTO_MIN_EDGES = 5_000_000
BDENSE_AUTO_MIN_FRAC = 0.15

# largest edge multiplicity a u4-packed A-table can hold — the ONE
# place the 4-bit limit lives (pack_a_u4 and both stacked builders'
# packability decisions consume it)
U4_MAX = 15


@dataclass
class BlockPlan:
    """Host-built dense-tile layout + residual CSR (static per graph).

    a_blocks: uint8 [nblk, 128, 128] edge multiplicities (the planted
      generators emit duplicate edges; segment-sum semantics require
      counts, not 0/1) — OR, after :func:`pack_a_u4`, uint4-packed
      [nblk, 128, 64] with two multiplicities per byte (low nibble =
      even column); consumers must check the trailing axis before
      indexing columns directly.
    src_blk/dst_blk: int32 [nblk] tile ids, sorted by dst_blk (the
      output scatter-add sees sorted indices).
    res_row_ptr/res_col: the residual dst-major CSR (edges in blocks
      under ``min_fill`` + multiplicities over 255), aggregated by the
      caller through the sectioned/ELL path.
    """
    num_rows: int
    vpad: int
    a_blocks: np.ndarray
    src_blk: np.ndarray
    dst_blk: np.ndarray
    res_row_ptr: np.ndarray
    res_col: np.ndarray
    dense_edges: int
    total_edges: int
    # source tile space (== vpad for the square single-device plan;
    # the distributed planner tiles local dst rows x GATHERED source
    # coordinates, so src_vpad covers num_cols instead)
    src_vpad: int = 0
    # zero-A group-alignment blocks appended by pad_plan_groups (the
    # group they enable is the kernel's ``group`` argument)
    pad_blocks: int = 0

    def __post_init__(self):
        if not self.src_vpad:
            self.src_vpad = self.vpad

    @property
    def n_blocks(self) -> int:
        return int(self.a_blocks.shape[0])

    def occupancy(self) -> dict:
        """The stats that decide whether this path can win (recorded
        with every race row).  ``mean_fill`` is over the RAW (edge-
        carrying) blocks — inert group padding must not dilute the
        evidence behind the min-fill breakeven; ``a_bytes`` is the
        real device table incl. padding."""
        nb = self.n_blocks
        raw = nb - self.pad_blocks
        occ = {
            "n_blocks": nb,
            "dense_edges": int(self.dense_edges),
            "dense_frac": round(self.dense_edges
                                / max(self.total_edges, 1), 4),
            "mean_fill": round(self.dense_edges / max(raw, 1), 1),
            # real device bytes — halved when pack_a_u4 applied
            "a_bytes": int(self.a_blocks.nbytes),
        }
        if self.pad_blocks:
            occ["pad_blocks"] = int(self.pad_blocks)
        return occ


def _select_dense(counts: np.ndarray, min_fill: int,
                  a_budget_bytes: Optional[int],
                  group: int = 1,
                  dst_of: Optional[np.ndarray] = None) -> np.ndarray:
    """Boolean selection over the occupied-tile census: at least
    ``min_fill`` edges, densest-first under the A-table budget.  ONE
    place for the rule — the native and numpy plan paths share it.

    With ``group > 1`` the budget applies to the table AFTER
    :func:`pad_plan_groups` alignment (up to ``group-1`` zero blocks
    per occupied dst tile) — padding must never silently defeat the
    byte cap the budget exists to enforce.  ``dst_of`` gives each
    candidate's dst tile id; the padded size is monotone in the
    number of kept blocks (a new block either fills an existing
    group's padding slot or opens one new group), so a binary search
    finds the largest densest-first prefix that fits."""
    dense_sel = counts >= min_fill
    if a_budget_bytes is None:
        return dense_sel
    bb = BLOCK * BLOCK
    cand = np.flatnonzero(dense_sel)
    order = cand[np.argsort(-counts[cand], kind="stable")]
    if group > 1:
        assert dst_of is not None

        def fits(k: int) -> bool:
            if k == 0:
                return True
            w = np.bincount(dst_of[order[:k]])
            padded = int((-(-w[w > 0] // group) * group).sum())
            return padded * bb <= a_budget_bytes

        keep_n = len(order)
        if not fits(keep_n):
            lo, hi = 0, keep_n
            while lo < hi:          # max k with fits(k); fits(lo) holds
                mid = (lo + hi + 1) // 2
                if fits(mid):
                    lo = mid
                else:
                    hi = mid - 1
            keep_n = lo
    else:
        keep_n = min(len(order), int(a_budget_bytes // bb))
    if keep_n < len(order):
        dense_sel = np.zeros_like(dense_sel)
        dense_sel[order[:keep_n]] = True
    return dense_sel


def plan_blocks(row_ptr: np.ndarray, col_idx: np.ndarray,
                num_rows: int, min_fill: int = 64,
                a_budget_bytes: Optional[int] = 2 << 30,
                num_cols: Optional[int] = None,
                group: int = 1,
                census: Optional[Tuple[np.ndarray, np.ndarray]] = None
                ) -> BlockPlan:
    """Tile the dst-major CSR into [128, 128] blocks; blocks with at
    least ``min_fill`` edges go dense, the rest stay residual CSR.

    ``a_budget_bytes`` caps the total uint8 A-table size (16 KiB per
    block): when more blocks qualify than fit the budget, the DENSEST
    are kept — fill, not count, is what amortizes the per-block cost,
    and an unbounded plan is unusable anyway (at Reddit scale with
    65k-row communities ~930k blocks qualify = a 15 GiB A-table that
    no 16 GiB chip can hold).  ``None`` disables the cap.

    ``num_cols`` sets a RECTANGULAR tile space: dst rows stay
    ``num_rows`` but source ids may range over ``num_cols`` (the
    distributed planner's local-rows x gathered-coordinates case).
    Default: square (``num_rows``).

    ``group > 1`` returns a :func:`pad_plan_groups`-aligned plan for
    the kernel's grouped output-tile reduction; the budget then caps
    the PADDED table (the selection accounts for alignment blocks up
    front — see _select_dense).

    ``census`` is an optional precomputed ``(keys, counts)`` from
    :func:`probe_dense_frac` over the SAME (num_rows, num_cols) tile
    space — the auto probe's O(E) walk is then not repeated (native
    path only; the numpy fallback recomputes)."""
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    col_i32 = np.ascontiguousarray(col_idx, dtype=np.int32)
    E = col_i32.shape[0]
    vpad = -(-num_rows // BLOCK) * BLOCK
    if num_cols is None:
        num_cols = num_rows
    src_vpad = -(-num_cols // BLOCK) * BLOCK
    n_tiles = src_vpad // BLOCK    # tiles per dst-tile row of keys

    from .. import native
    if native.available():
        # native census + fill: O(E) CSR walks (seconds at Reddit
        # scale vs ~15 min for the numpy argsort/unique pipeline);
        # byte-identical plans (tested).  col stays int32 throughout —
        # Graph.col_idx already is, so no full-E copies happen here
        keys_all, counts_all = census if census is not None \
            else native.block_counts(
                row_ptr, col_i32, num_rows, BLOCK, num_cols=num_cols)
        dense_keys = keys_all[_select_dense(
            counts_all, min_fill, a_budget_bytes, group=group,
            dst_of=keys_all // n_tiles)]
        a, res_ptr, res_col = native.block_fill(
            row_ptr, col_i32, num_rows, BLOCK, dense_keys,
            num_cols=num_cols)
        return pad_plan_groups(BlockPlan(
            num_rows=num_rows, vpad=vpad, a_blocks=a,
            src_blk=(dense_keys % n_tiles).astype(np.int32),
            dst_blk=(dense_keys // n_tiles).astype(np.int32),
            res_row_ptr=res_ptr, res_col=res_col,
            dense_edges=E - res_col.shape[0], total_edges=E,
            src_vpad=src_vpad), group)

    # numpy fallback works in int64 key space
    col_idx = col_i32.astype(np.int64)
    if E and (col_idx.min() < 0 or col_idx.max() >= num_cols):
        # same hard error as the native path's kErrValue — an
        # out-of-range source would otherwise build a key outside the
        # declared tile space and aggregate silently wrong
        raise ValueError(
            f"col_idx out of range [0, {num_cols}) for the declared "
            f"source space")
    deg = np.diff(row_ptr)
    dst_all = np.repeat(np.arange(num_rows, dtype=np.int64), deg)
    key = (dst_all // BLOCK) * n_tiles + col_idx // BLOCK
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    blocks, starts, counts = np.unique(key_s, return_index=True,
                                       return_counts=True)
    dense_sel = _select_dense(counts, min_fill, a_budget_bytes,
                              group=group, dst_of=blocks // n_tiles)
    dense_blocks = blocks[dense_sel]
    nblk = int(dense_blocks.shape[0])
    a = np.zeros((nblk, BLOCK, BLOCK), dtype=np.uint8)
    if nblk:
        pos = np.searchsorted(dense_blocks, key_s)
        pos_c = np.minimum(pos, nblk - 1)
        in_dense = dense_blocks[pos_c] == key_s
    else:
        in_dense = np.zeros(E, dtype=bool)
    e_sel = order[in_dense]
    if nblk:
        flat = (pos_c[in_dense] * BLOCK * BLOCK
                + (dst_all[e_sel] % BLOCK) * BLOCK
                + (col_idx[e_sel] % BLOCK))
        # occupied-slot counting stays O(E_dense), never O(slots):
        # a global bincount over nblk*16384 slots is ~17 GiB of
        # transient int64 at the default A budget (round-5 advisor)
        flat_order = np.argsort(flat, kind="stable")
        flat_sorted = flat[flat_order]
        slots, counts_s = np.unique(flat_sorted, return_counts=True)
        # uint8 multiplicity with saturation: overflowing edges (deep
        # duplicates past 255) fall back to the residual CSR so the
        # semantics stay exact
        kept = np.minimum(counts_s, 255)
        a.reshape(-1)[slots] = kept.astype(np.uint8)
        dense_edges = int(kept.sum())
        overflow_edges = int((counts_s - kept).sum())
    else:
        dense_edges = 0
        overflow_edges = 0
    # residual = all edges not counted densely
    res_mask = np.ones(E, dtype=bool)
    res_mask[e_sel] = False
    if overflow_edges:
        # mark the LAST `excess` duplicates of each saturated slot
        # residual (rare pathological multi-edges)
        over = counts_s > 255
        s1 = np.searchsorted(flat_sorted, slots[over], side="right")
        for hi, ex in zip(s1, (counts_s[over] - 255)):
            res_mask[e_sel[flat_order[hi - ex:hi]]] = True
    res_dst = dst_all[res_mask]
    res_col = col_idx[res_mask]
    res_deg = np.bincount(res_dst, minlength=num_rows)
    res_ptr = np.zeros(num_rows + 1, dtype=np.int64)
    np.cumsum(res_deg, out=res_ptr[1:])
    # residual edges arrive dst-sorted already (dst_all is sorted)
    return pad_plan_groups(BlockPlan(
        num_rows=num_rows, vpad=vpad,
        a_blocks=a,
        src_blk=(dense_blocks % n_tiles).astype(np.int32),
        dst_blk=(dense_blocks // n_tiles).astype(np.int32),
        res_row_ptr=res_ptr, res_col=res_col.astype(np.int32),
        dense_edges=dense_edges, total_edges=E,
        src_vpad=src_vpad), group)


def probe_dense_frac(row_ptr: np.ndarray, col_idx: np.ndarray,
                     num_rows: int, min_fill: int = 64,
                     a_budget_bytes: Optional[int] = 2 << 30,
                     num_cols: Optional[int] = None,
                     group: int = 1, return_census: bool = False):
    """Census-only estimate of the edge fraction a bdense plan would
    put on dense tiles — the ``aggr_impl='auto'`` structure probe.

    Runs the native O(E) tile census + the budget selection but skips
    the A fill (the expensive half of planning), so ``auto`` can
    decide sectioned-vs-bdense in ~a second at Reddit scale.  Returns
    None without librocio — the numpy census costs minutes at the
    scales where probing matters, and ``auto`` must never be slower
    than what it replaces.  (The estimate ignores uint8 saturation
    overflow — pathological >255-multiplicity edges land in the
    residual at plan time; negligible for the decision.)

    ``return_census=True`` returns ``(frac, (keys, counts))`` so a
    following :func:`plan_blocks` call over the SAME tile space can
    reuse the census instead of re-walking the CSR."""
    from .. import native
    if not native.available():
        return None
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    col_i32 = np.ascontiguousarray(col_idx, dtype=np.int32)
    E = col_i32.shape[0]
    if num_cols is None:
        num_cols = num_rows
    n_tiles = -(-num_cols // BLOCK)
    if E == 0:
        empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))
        return (0.0, empty) if return_census else 0.0
    keys, counts = native.block_counts(row_ptr, col_i32, num_rows,
                                       BLOCK, num_cols=num_cols)
    sel = _select_dense(counts, min_fill, a_budget_bytes, group=group,
                        dst_of=keys // n_tiles)
    # host-side numpy census in the planning probe; no device array
    # within sight: roc-lint: ok=host-sync-hot-path
    frac = float(counts[sel].sum()) / E
    return (frac, (keys, counts)) if return_census else frac


def pad_plan_groups(plan: BlockPlan, group: int) -> BlockPlan:
    """Pad each dst tile's block run to a multiple of ``group`` with
    zero-A blocks (src tile 0 — A==0 makes the contribution zero), so
    :func:`aggregate_block_dense` can reduce ``group`` blocks per
    output-tile update (``group=...``).

    Why: with group=1 every dense block costs one read-modify-write
    of a [128, F] fp32 output tile (~256 KiB at F=256) — the DOMINANT
    HBM traffic of the path (A is 16 KiB, the source tile 64 KiB
    bf16).  Blocks are already dst-major sorted, so padding runs to a
    group multiple lets one einsum reduce a whole group in registers
    and write each output tile ``group``x less often.  Padding
    overhead is <= (group-1) blocks per OCCUPIED dst tile — a few
    percent at the measured widths (mean 213 blocks/tile on the
    planted-community substrate at Reddit scale)."""
    if group <= 1 or plan.n_blocks == 0:
        return plan
    dst = plan.dst_blk
    uniq, counts = np.unique(dst, return_counts=True)
    padded = -(-counts // group) * group
    total = int(padded.sum())
    if total == plan.n_blocks:
        return plan
    new_start = np.zeros(len(uniq) + 1, np.int64)
    np.cumsum(padded, out=new_start[1:])
    old_start = np.zeros(len(uniq) + 1, np.int64)
    np.cumsum(counts, out=old_start[1:])
    run_id = np.repeat(np.arange(len(uniq)), counts)
    pos = (new_start[run_id]
           + (np.arange(plan.n_blocks) - old_start[run_id]))
    a2 = np.zeros((total, BLOCK, BLOCK), np.uint8)
    a2[pos] = plan.a_blocks
    src2 = np.zeros(total, np.int32)
    src2[pos] = plan.src_blk
    dst2 = np.repeat(uniq, padded).astype(np.int32)
    return replace(plan, a_blocks=a2, src_blk=src2, dst_blk=dst2,
                   pad_blocks=plan.pad_blocks
                   + (total - plan.n_blocks))


def plan_blocks_packed(row_ptr: np.ndarray, col_idx: np.ndarray,
                       num_rows: int, min_fill: int = 64,
                       a_budget_bytes: Optional[int] = 2 << 30,
                       num_cols: Optional[int] = None,
                       group: int = 1,
                       census=None) -> BlockPlan:
    """:func:`plan_blocks` + the u4 packing budget policy — ONE home
    for the rule: plan against
    DOUBLE the A budget first, since :func:`pack_a_u4` halves device
    bytes and a packable graph can afford 2x the blocks within the
    stated cap; unpackable plans (multi-edge hubs past 4 bits — rare)
    re-plan at the true budget, reusing ``census`` so only the fill
    repeats."""
    budget2 = (a_budget_bytes * 2
               if a_budget_bytes is not None else None)
    plan = plan_blocks(row_ptr, col_idx, num_rows, min_fill=min_fill,
                       a_budget_bytes=budget2, num_cols=num_cols,
                       group=group, census=census)
    p4 = pack_a_u4(plan)
    if p4 is not None:
        return p4
    if a_budget_bytes is not None \
            and plan.a_blocks.nbytes > a_budget_bytes:
        plan = plan_blocks(row_ptr, col_idx, num_rows,
                           min_fill=min_fill,
                           a_budget_bytes=a_budget_bytes,
                           num_cols=num_cols, group=group,
                           census=census)
    return plan


def pack_a_u4(plan: BlockPlan) -> Optional[BlockPlan]:
    """Pack the uint8 A-table to uint4 (two multiplicities per byte,
    ``byte[..., k] = col 2k | col 2k+1 << 4``) — halves the A-table's
    HBM bytes AND its read traffic (~17% of the grouped dense path's
    per-block bytes).  Exact only when every multiplicity fits 4 bits;
    returns None otherwise (community plans almost always fit — the
    mean slot multiplicity is 1-2 — but a hub-multiedge plan must
    fall back to uint8 rather than saturate silently).

    The kernel detects packing from the trailing axis
    (``BLOCK // 2``) and unpacks in-register per chunk.  Applied on
    the single-device path (make_graph_context) and by
    the stacked distributed/multihost builders — all parts pack or
    none (one uniform SPMD trailing width; multihost agrees the
    global max multiplicity via one extra O(P) collective)."""
    if plan.n_blocks and plan.a_blocks.max() > U4_MAX:
        return None
    # an EMPTY plan packs too (to [0, 128, 64]): the stacked
    # distributed builders need one uniform trailing width across
    # parts, and a zero-block part must not force uint8 on the rest
    a = plan.a_blocks
    packed = (a[..., 0::2] | (a[..., 1::2] << 4)).astype(np.uint8)
    return replace(plan, a_blocks=packed)


def aggregate_block_dense(x: jax.Array, a_blocks: jax.Array,
                          src_blk: jax.Array, dst_blk: jax.Array,
                          num_rows: int, vpad: int,
                          out_dtype=jnp.float32,
                          chunk_blocks: int = _CHUNK_BLOCKS,
                          src_vpad: int = 0,
                          group: int = 1,
                          scale_dst: Optional[jax.Array] = None,
                          scale_src: Optional[jax.Array] = None
                          ) -> jax.Array:
    """Dense-tile partial aggregation (the residual CSR is the
    caller's, via the sectioned/ELL path on the SAME x).

    x: [src_rows, F] source features; ``src_vpad`` (default: ``vpad``)
    is the source tile space — equal to vpad for the square
    single-device plan, the padded GATHERED row count for the
    distributed per-partition plan (x then is the all-gathered
    matrix, dst tiles cover only this partition's local rows).
    Returns [num_rows, F] in ``out_dtype`` — fp32 accumulation over
    tiles (a hub tile receives many sequential adds).

    ``group > 1`` requires a :func:`pad_plan_groups`-padded plan
    (every run of ``group`` consecutive blocks shares one dst tile):
    each group is reduced in ONE einsum and its output tile updated
    once — ``group``x less output read-modify-write traffic.

    ``scale_dst`` [vpad] / ``scale_src`` [src_vpad] (optional, set
    together): per-row fp32 scales of the fused normalization
    ``D^-1/2 A D^-1/2`` (train fused path).  Applied per tile
    IN-REGISTER around the einsum — the integer A-table (and its u4
    packing) stays untouched and no extra HBM pass happens: the
    source tile is scaled after its load, the fp32 accumulator before
    its scatter-add.
    """
    F = x.shape[1]
    nblk = a_blocks.shape[0]
    n_tiles = vpad // BLOCK
    src_vpad = src_vpad or vpad
    src_rows = min(x.shape[0], src_vpad)
    if group > 1 and nblk % group:
        raise ValueError(
            f"group={group} needs a pad_plan_groups-padded plan; "
            f"got {nblk} blocks")
    if (scale_dst is None) != (scale_src is None):
        raise ValueError("scale_dst and scale_src must be set together")
    xt = jnp.zeros((src_vpad, F), dtype=x.dtype).at[:src_rows].set(
        x[:src_rows]).reshape(src_vpad // BLOCK, BLOCK, F)
    # pad the block list to a chunk multiple; padding scatters zero
    # tiles into a dummy output tile.  Small plans shrink the chunk so
    # padding never exceeds one chunk's worth of zero work.
    group = max(1, group)
    chunk_blocks = max(group, min(chunk_blocks, nblk)
                       // group * group)
    chunks = max(1, -(-nblk // chunk_blocks))
    pad = chunks * chunk_blocks - nblk
    # uint4-packed A (pack_a_u4) is detected from the trailing axis
    a_w = a_blocks.shape[-1]
    packed = a_w == BLOCK // 2
    a_p = jnp.concatenate([
        a_blocks,
        jnp.zeros((pad, BLOCK, a_w), dtype=a_blocks.dtype)]) \
        if pad else a_blocks
    s_p = jnp.concatenate([src_blk,
                           jnp.zeros(pad, dtype=src_blk.dtype)]) \
        if pad else src_blk
    d_p = jnp.concatenate([dst_blk,
                           jnp.full(pad, n_tiles, dtype=dst_blk.dtype)]) \
        if pad else dst_blk
    compute = (jnp.bfloat16 if x.dtype in (jnp.bfloat16,)
               else jnp.float32)
    if scale_src is not None:
        # tiled scale views: [n_src_tiles, 128] / [n_tiles + 1, 128]
        # (the trailing zero row serves padding blocks' dummy dst
        # tile).  Source scaling runs in the compute dtype — exactly
        # where the unfused indegree_norm multiplied; the dst side
        # scales the fp32 accumulator.
        ssrc_t = scale_src.astype(compute).reshape(
            src_vpad // BLOCK, BLOCK)
        sdst_t = jnp.concatenate([
            scale_dst.astype(jnp.float32).reshape(n_tiles, BLOCK),
            jnp.zeros((1, BLOCK), jnp.float32)])
    else:
        ssrc_t = sdst_t = None

    def body(out, ch):
        a_u8, s_ids, d_ids = ch
        if packed:
            # in-register uint4 unpack: byte k holds cols 2k / 2k+1
            a_u8 = jnp.stack([a_u8 & 0xF, a_u8 >> 4],
                             axis=-1).reshape(a_u8.shape[0],
                                              BLOCK, BLOCK)
        gx = xt[s_ids].astype(compute)              # [C, 128, F]
        if ssrc_t is not None:
            gx = gx * ssrc_t[s_ids][:, :, None]
        if group > 1:
            C = s_ids.shape[0]
            y = jnp.einsum("gwij,gwjf->gif",
                           a_u8.astype(compute).reshape(
                               C // group, group, BLOCK, BLOCK),
                           gx.reshape(C // group, group, BLOCK, F),
                           preferred_element_type=jnp.float32)
            d_ids = d_ids.reshape(C // group, group)[:, 0]
        else:
            y = jnp.einsum("bij,bjf->bif", a_u8.astype(compute), gx,
                           preferred_element_type=jnp.float32)
        if sdst_t is not None:
            y = y * sdst_t[d_ids][:, :, None]
        # several blocks/groups can share a dst tile within one chunk
        # -> NOT unique; the plan's dst-major sort keeps them sorted
        return out.at[d_ids].add(y, indices_are_sorted=True), None

    out0 = jnp.zeros((n_tiles + 1, BLOCK, F), dtype=jnp.float32)
    C = chunk_blocks
    out, _ = lax.scan(
        body, out0,
        (a_p.reshape(chunks, C, BLOCK, a_w),
         s_p.reshape(chunks, C), d_p.reshape(chunks, C)))
    return out[:n_tiles].reshape(vpad, F)[:num_rows].astype(out_dtype)

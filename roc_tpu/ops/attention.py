"""Graph attention aggregation (GAT): the edge softmax and its weighted
neighbor sum, on the degree-bucketed ELL layout and on the uniform
width-8 flat layout.

The reference implements only unweighted CSR sum aggregation
(``scattergather_kernel.cu:20-76``); attention is the framework's
TPU-native extension for the GAT model family (Velickovic et al.,
ICLR'18 — additive attention, K heads side by side on the feature
axis, each over its own ``dh``-wide slice):

    e_ij^k   = LeakyReLU(a_src^k . h_j^k + a_dst^k . h_i^k)  for j in N(i)
    alpha^k  = softmax_j(e_ij^k)
    out_i^k  = sum_j alpha_ij^k h_j^k

The ELL layout makes the edge softmax *exact and scatter-free*: every
row's whole neighborhood lives in ONE bucket row (bucket width >= the
row's degree, ``core/ell.py row_widths``), so the per-row max /
exp-sum / weighted sum are all reductions over the bucket's width
axis with padding masked — no segment ops, no two-pass global
normalization.  This is also why the ``sectioned`` layout cannot host
attention: it splits a row's neighbors across source sections, which
would require a cross-section softmax reduction (use ``ell``).

Gradients are plain autodiff: attention is nonlinear in both inputs,
so the reference's symmetric kernel-reuse trick does not apply.

Every operation sits under one of three phase scopes (obs/scopes.py
``ATTN_*_SCOPE``), nested inside the model op's ``roc.agg.op<i>``:
``scores`` (the per-vertex ``s``/``t`` projections, their per-edge
gather, LeakyReLU, the padding mask), ``stats`` (row max, ``exp``,
denominator) and ``gather`` (the feature gather, the weighted sum —
the numerator — and the division).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import (ATTN_GATHER_SCOPE as _GATHER,
                          ATTN_SCORES_SCOPE as _SCORES,
                          ATTN_STATS_SCOPE as _STATS)


def gat_aggregate_ell(full: jax.Array, s_full: jax.Array,
                      d_local: jax.Array, ell_idx, ell_row_id,
                      ell_row_pos: jax.Array, num_rows: int,
                      neg_slope: float = 0.2,
                      budget_elems: int = 1 << 24) -> jax.Array:
    """Attention-weighted neighbor aggregation over ELL buckets,
    multi-head: K heads attend independently over the same
    neighborhood and their outputs concatenate (the GAT paper's
    concat form; K == 1 is single-head).

    full: [G+1, K*dh] gathered features with trailing zero row (the
      halo result; G == gathered_rows); the feature axis is the K
      head slices of width dh, concatenated.
    s_full: [G+1, K] per-source logits ``a_src^k . h_j^k`` with the
      dummy slot LAST (its value is irrelevant — dummy edges are
      masked).
    d_local: [num_rows + 1, K] per-destination logits with a trailing
      dummy slot for padding bucket rows.
    ell_idx / ell_row_id / ell_row_pos: core/ell.py EllTable arrays
      (single-partition views).
    Rows with no neighbors return 0 (the sum path's convention).

    Large buckets are row-segmented with ``lax.scan`` under the same
    ``budget_elems`` transient bound as the sum/max paths.  The
    per-(row, width) transient is the [K*dh] feature gather PLUS the
    fp32 score tensors (e / w / alpha, [K] each) — at many heads and
    narrow head width the scores rival the gather, so the budget math
    counts both.
    """
    F = full.shape[1]
    K = s_full.shape[1]
    assert F % K == 0, (F, K)
    # elements per (row, width) slot the segmentation must bound
    unit = F + 3 * K
    dummy = full.shape[0] - 1
    neg = jnp.asarray(-jnp.inf, dtype=jnp.float32)

    def seg_out(idx_seg, rid_seg):
        # scores softmax in fp32 for stability regardless of compute
        # dtype (bf16 exp over a wide range loses the tail)
        with jax.named_scope(_SCORES):
            e = (s_full[idx_seg].astype(jnp.float32)
                 + d_local[rid_seg].astype(jnp.float32)[:, None, :])
            e = jax.nn.leaky_relu(e, neg_slope)          # [r, w, K]
            valid = (idx_seg != dummy)[:, :, None]
            e = jnp.where(valid, e, neg)
        with jax.named_scope(_STATS):
            m = jnp.max(e, axis=1, keepdims=True)
            # all-padding rows have m == -inf; zero them via the guard
            w = jnp.where(valid, jnp.exp(e - jnp.where(
                jnp.isfinite(m), m, 0.0)), 0.0)
            den = jnp.maximum(w.sum(axis=1, keepdims=True), 1e-20)
        with jax.named_scope(_GATHER):
            alpha = (w / den).astype(full.dtype)         # [r, w, K]
            g = full[idx_seg].reshape(*idx_seg.shape, K, F // K)
            return jnp.einsum("rwk,rwkd->rkd", alpha,
                              g).reshape(idx_seg.shape[0], F)

    outs = []
    for idx, rid in zip(ell_idx, ell_row_id):
        R, W = idx.shape
        if R * W * unit <= budget_elems:
            outs.append(seg_out(idx, rid))
            continue
        # NOTE (compile size): every bucket that lands here emits its
        # own checkpointed scan, and autodiff doubles each — at
        # products scale (lognormal degrees -> ~18 width buckets) the
        # unrolled HLO pushed remote compile past 40 min.  Large-graph
        # attention therefore routes through gat_aggregate_flat8
        # (ONE uniform scan shape) — see resolve_attention_impl.
        segs = -(-R * W * unit // budget_elems)
        seg_rows = -(-R // segs)
        Rp = seg_rows * segs
        idx_p = jnp.concatenate(
            [idx, jnp.full((Rp - R, W), dummy, dtype=idx.dtype)], axis=0)
        rid_p = jnp.concatenate(
            [rid, jnp.full((Rp - R,), num_rows, dtype=rid.dtype)],
            axis=0)

        # remat each step: WITHOUT it, autodiff saves every step's
        # [seg_rows, W, F] feature gather as a stacked scan residual —
        # [segs, seg_rows, W, F] = 18.5 GiB at products scale
        # (observed OOM, v5e 2026-07-30).  Attention is nonlinear, so
        # unlike the sum path the backward genuinely needs the
        # gathered values; recomputing them per step in the backward
        # sweep bounds memory at one step's transient.
        seg_out_ckpt = jax.checkpoint(seg_out)

        def body(_, ch):
            return None, seg_out_ckpt(*ch)

        _, segs_out = lax.scan(body, None,
                               (idx_p.reshape(segs, seg_rows, W),
                                rid_p.reshape(segs, seg_rows)))
        outs.append(segs_out.reshape(Rp, F)[:R])
    with jax.named_scope(_GATHER):
        zero = jnp.zeros((1, F), dtype=full.dtype)
        cat = jnp.concatenate(outs + [zero], axis=0)
        return cat[ell_row_pos]


def resolve_dh_chunk(num_rows: int, heads: int, dh: int,
                     carry_budget: int = 768 << 20) -> Optional[int]:
    """Per-head feature-dim chunk width for :func:`gat_aggregate_flat8`.

    The numerator scan carries ``[num_rows+1, heads*dh]`` fp32; at
    ogbn-products scale (V=2.45M, F=256) that is 2.5 GiB, and its
    backward cotangent doubles it — the measured single-chip OOM
    (16.61 G of 15.75 G HBM, 2026-07-31).  Chunking dh re-runs the
    score computation per slice (one extra ``s_full`` gather pass,
    ~E*K bytes — negligible next to the feature gather) in exchange
    for an O(1/n_chunks) carry.

    ``carry_budget`` caps the TRAINING-time peak: the chunk is sized
    against 2x the forward carry (forward + its backward cotangent
    live simultaneously — round-5 advisor: sizing against the forward
    alone made the guarantee inference-only).  Returns None when the
    doubled carry fits ``carry_budget``."""
    bytes_per_dh = (num_rows + 1) * heads * 4
    # the cotangent doubles the live carry in training
    train_budget = carry_budget // 2
    if bytes_per_dh * dh <= train_budget:
        return None
    # chunk width straight from the budget so the per-chunk carry is
    # GUARANTEED to fit (a ceil-of-ceil split can overshoot ~2x)
    return max(1, min(dh, train_budget // bytes_per_dh))


def gat_aggregate_flat8(full: jax.Array, s_full: jax.Array,
                        d_local: jax.Array, f8_idx: jax.Array,
                        f8_dst: jax.Array, num_rows: int,
                        neg_slope: float = 0.2,
                        dh_chunk: Optional[int] = None) -> jax.Array:
    """Attention aggregation over the UNIFORM width-8 sub-row layout —
    the large-graph form (same numerics as :func:`gat_aggregate_ell`,
    different reduction structure).

    The bucket path's per-width Python unrolling emits one
    checkpointed scan per large bucket and autodiff doubles each; at
    ogbn-products scale that HLO exceeded practical remote-compile
    time (>40 min, VERDICT r3).  Here every row's neighborhood is
    split into width-8 sub-rows in ONE ``[n_chunks, seg_rows, 8]``
    table (built by ``core/ell.py sectioned_from_graph`` with a single
    section spanning all sources, so ids are global and sub-rows of a
    row are consecutive/ascending), and the edge softmax becomes two
    uniform scans:

      pass 1  per-sub-row score max, combined per row with a sorted
              scatter-max (stop_gradient: softmax is invariant to the
              shift, so the max needs no backward);
      pass 2  w = exp(e - rowmax) masked; numerator (w-weighted
              feature gather-sum) and denominator scatter-added per
              row; out = num / den.

    One scan body shape total — compile size is independent of the
    degree distribution.

    full: [G+1, K*dh] gathered features, trailing zero row (== the
      dummy id in ``f8_idx``).
    s_full: [G+1, K]; d_local: [num_rows+1, K] (trailing dummy slot,
      ``f8_dst`` padding points at it).
    """
    F = full.shape[1]
    K = s_full.shape[1]
    assert F % K == 0, (F, K)
    dummy = full.shape[0] - 1
    neg = jnp.asarray(-jnp.inf, dtype=jnp.float32)

    def scores(idx_ch, dst_ch):
        with jax.named_scope(_SCORES):
            e = (s_full[idx_ch].astype(jnp.float32)
                 + d_local[dst_ch].astype(jnp.float32)[:, None, :])
            e = jax.nn.leaky_relu(e, neg_slope)        # [seg, 8, K]
            valid = (idx_ch != dummy)[:, :, None]
            return jnp.where(valid, e, neg), valid

    def weights(idx_ch, dst_ch):
        e, valid = scores(idx_ch, dst_ch)
        with jax.named_scope(_STATS):
            return jnp.where(
                valid, jnp.exp(e - rowmax[dst_ch][:, None, :]), 0.0)

    def pass1(rm, ch):
        e, _ = scores(*ch)
        with jax.named_scope(_STATS):
            m8 = jnp.max(e, axis=1)                    # [seg, K]
            return rm.at[ch[1]].max(m8, indices_are_sorted=True), None

    with jax.named_scope(_STATS):
        rm0 = jnp.full((num_rows + 1, K), -jnp.inf, dtype=jnp.float32)
    rowmax, _ = lax.scan(jax.checkpoint(pass1), rm0, (f8_idx, f8_dst))
    # rows with no finite score (no neighbors) shift by 0; softmax is
    # shift-invariant so the max carries no gradient
    with jax.named_scope(_STATS):
        rowmax = lax.stop_gradient(
            jnp.where(jnp.isfinite(rowmax), rowmax, 0.0))

    dh = F // K

    def add_den(den, w, dst_ch):
        with jax.named_scope(_STATS):
            return den.at[dst_ch].add(w.sum(axis=1),
                                      indices_are_sorted=True)

    def add_num(num, w, src, idx_ch, dst_ch):
        """``num[dst] += sum_w w * src[idx]`` for one chunk, per head,
        ``src`` [G+1, K*dc].  The numerator carry stays fp32: a hub
        row of degree d receives d/8 sequential scatter-adds of
        full-magnitude partials — accumulating those in bf16 would
        lose low-order bits every add (the bucket path reduces a whole
        row in one fp32-MXU einsum, and this path must match its
        numerics)."""
        with jax.named_scope(_GATHER):
            g = src[idx_ch].reshape(*idx_ch.shape, K, -1)
            part = jnp.einsum("swk,swkd->skd", w.astype(src.dtype), g,
                              preferred_element_type=jnp.float32
                              ).reshape(idx_ch.shape[0], src.shape[1])
            return num.at[dst_ch].add(part, indices_are_sorted=True)

    def divide(num, den):
        with jax.named_scope(_GATHER):
            den = jnp.maximum(den[:num_rows], 1e-20)
            numr = num[:num_rows].reshape(num_rows, K, -1)
            return (numr / den[:, :, None]).astype(full.dtype)

    with jax.named_scope(_STATS):
        den0 = jnp.zeros((num_rows + 1, K), dtype=jnp.float32)
    if dh_chunk is None or dh_chunk >= dh:
        def pass2(carry, ch):
            num, den = carry
            idx_ch, dst_ch = ch
            w = weights(idx_ch, dst_ch)                # [seg, 8, K]
            return (add_num(num, w, full, idx_ch, dst_ch),
                    add_den(den, w, dst_ch)), None

        with jax.named_scope(_GATHER):
            num0 = jnp.zeros((num_rows + 1, F), dtype=jnp.float32)
        (num, den), _ = lax.scan(jax.checkpoint(pass2), (num0, den0),
                                 (f8_idx, f8_dst))
        return divide(num, den).reshape(num_rows, F)

    # dh-chunked numerator (resolve_dh_chunk): the fused pass2 carry
    # is [num_rows+1, F] fp32 and autodiff doubles it — the products-
    # scale OOM.  Scores are cheap (one [G+1, K] gather per pass), so
    # the denominator gets its own scan and each dh slice re-derives w
    # while carrying only [num_rows+1, K*dc] fp32.  Per-element math
    # and scatter-add order match the fused form (tested to <=3e-7;
    # XLA lowers non-dividing slice widths slightly differently).
    def passden(den, ch):
        return add_den(den, weights(*ch), ch[1]), None

    den, _ = lax.scan(jax.checkpoint(passden), den0,
                      (f8_idx, f8_dst))
    fullr = full.reshape(full.shape[0], K, dh)
    outs = []
    for lo in range(0, dh, dh_chunk):
        dc = min(dh_chunk, dh - lo)
        # materialize the slice once per chunk ([G+1, K*dc]) so the
        # scan gathers dc-wide rows, not F-wide ones
        with jax.named_scope(_GATHER):
            full_c = lax.slice_in_dim(fullr, lo, lo + dc, axis=2) \
                .reshape(full.shape[0], K * dc)
            num0 = jnp.zeros((num_rows + 1, K * dc), dtype=jnp.float32)

        def pass2c(num, ch, full_c=full_c):
            return add_num(num, weights(*ch), full_c, *ch), None

        num, _ = lax.scan(jax.checkpoint(pass2c), num0,
                          (f8_idx, f8_dst))
        outs.append(divide(num, den))
    with jax.named_scope(_GATHER):
        return jnp.concatenate(outs, axis=2).reshape(num_rows, F)

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

Gradients: the forward is scatter-free, and on a symmetric graph so is
the bucketed layout's backward — the reference's design (the backward
is the same kernel on the transposed graph) holds for attention too,
because an edge's weight is a function of per-vertex quantities alone
(:func:`gat_ell_backward`).  A directed graph and the flat layout take
plain autodiff through the forward; there every scan step scatter-adds
into a whole ``[G+1, .]`` cotangent.

Every operation sits under one of three phase scopes (obs/scopes.py
``ATTN_*_SCOPE``), nested inside the model op's ``roc.agg.op<i>``:
``scores`` (the per-vertex ``s``/``t`` projections, their per-edge
gather, LeakyReLU, the padding mask), ``stats`` (row max, ``exp``,
denominator) and ``gather`` (the feature gather, the weighted sum —
the numerator — and the division).

Dot-product attention (the Graph Transformer layer of UniMP, Shi et
al., IJCAI'21; PyTorch Geometric's ``TransformerConv``) shares the
bucket loop and nothing of the tiles: its score is a function of two
VECTORS, ``s_ij = q_i . k_j / sqrt(d)`` per head, so no per-vertex
scalar carries the destination's part of the gradient.  Its backward
on a symmetric graph is two scatter-free passes over the forward's own
tables — attention's dQ / dK-dV split on a graph
(:func:`dot_ell_backward`) — with ``rho_i = G_i . m_i`` per vertex.
Attention dropout is a per-edge mask drawn by a counter-based hash of
``(dst, src, head)`` under the step's key (:func:`edge_keep_scale`), so
the pass over the transposed table draws the forward's mask again.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import (ATTN_GATHER_SCOPE as _GATHER,
                          ATTN_SCORES_SCOPE as _SCORES,
                          ATTN_STATS_SCOPE as _STATS)


def _forward_tile(full, s_full, d_local, neg_slope, residuals=False):
    """``tile(idx_seg, rid_seg)``: the edge softmax and weighted sum of
    one ``[r, w]`` tile of bucket rows — the segment function the
    forward of both gradient paths shares.  It returns ``(out [r, F],)``;
    with ``residuals`` (the symmetric VJP's forward rule, taken while
    the tile is in hand) also ``c [r, F]`` fp32 and the row statistics
    ``[r, 2K]`` fp32 (row max, denominator).

    ``c_i = (1 - slope) (sum_j [z_ij >= 0] alpha_ij h_j - out_i
    sum_j [z_ij >= 0] alpha_ij)`` is what the destination score's
    gradient needs of the row's neighbors: ``d_bar_i = G_i . c_i``.
    LeakyReLU's slope is ``slope + (1 - slope) [z >= 0]`` and the
    constant part cancels (``sum_j alpha_ij (G_i . h_j - G_i . out_i)
    == 0``), so a row whose edges all sit on one side of 0 reads an
    exact 0 at any compute dtype."""
    F = full.shape[1]
    K = s_full.shape[1]
    dummy = full.shape[0] - 1
    neg = jnp.asarray(-jnp.inf, dtype=jnp.float32)

    def tile(idx_seg, rid_seg):
        # scores softmax in fp32 for stability regardless of compute
        # dtype (bf16 exp over a wide range loses the tail)
        with jax.named_scope(_SCORES):
            z = (s_full[idx_seg].astype(jnp.float32)
                 + d_local[rid_seg].astype(jnp.float32)[:, None, :])
            e = jax.nn.leaky_relu(z, neg_slope)          # [r, w, K]
            valid = (idx_seg != dummy)[:, :, None]
            e = jnp.where(valid, e, neg)
        with jax.named_scope(_STATS):
            m = jnp.max(e, axis=1, keepdims=True)
            # all-padding rows have m == -inf; zero them via the guard
            m = jnp.where(jnp.isfinite(m), m, 0.0)
            w = jnp.where(valid, jnp.exp(e - m), 0.0)
            den = jnp.maximum(w.sum(axis=1, keepdims=True), 1e-20)
        with jax.named_scope(_GATHER):
            soft = w / den
            alpha = soft.astype(full.dtype)              # [r, w, K]
            g = full[idx_seg].reshape(*idx_seg.shape, K, F // K)
            if not residuals:
                return (jnp.einsum("rwk,rwkd->rkd", alpha,
                                   g).reshape(idx_seg.shape[0], F),)
            # both sums as the forward takes its one: asked for in
            # fp32, XLA:TPU first writes the whole tile out in fp32
            num = jnp.einsum("rwk,rwkd->rkd", alpha, g)
            num_up = jnp.einsum("rwk,rwkd->rkd",
                                jnp.where(z >= 0, alpha, 0), g)
        with jax.named_scope(_STATS):
            # in fp32, not the rounded weights: 1 where every edge is up
            share_up = jnp.where(z >= 0, soft, 0.0).sum(axis=1)
            stats = jnp.concatenate([m[:, 0], den[:, 0]], axis=1)
        with jax.named_scope(_GATHER):
            c = (1.0 - neg_slope) * (
                num_up.astype(jnp.float32)
                - share_up[:, :, None] * num.astype(jnp.float32))
            return (num.reshape(idx_seg.shape[0], F),
                    c.reshape(idx_seg.shape[0], F), stats)

    return tile


def _transposed_tile(g_full, p_full, s_pad, x_pad, neg_slope):
    """``tile(idx_seg, rid_seg)`` of the symmetric backward: at bucket
    row ``j`` over its own slots ``i = idx[j, w]`` — the rows ``j`` is
    a source of, because stored edges are symmetric — recompute
    ``alpha_ij`` from row ``i``'s statistics and reduce over the width
    axis as the forward does:

        h_bar_j = sum_w alpha_ij G_i
        s_bar_j = sum_w alpha_ij (G_i . h_j - q_i) leaky'(s_j + d_i)

    g_full: [G+1, F] cotangent of the op's output through the halo;
    p_full: [G+1, 4K] fp32, per vertex ``d, m, den, q = G . out``;
    s_pad [num_rows+1, K] fp32 and x_pad [num_rows+1, F]: the rows'
    own source scores and features, a zero row last for padding bucket
    rows."""
    F = g_full.shape[1]
    K = s_pad.shape[1]
    dummy = g_full.shape[0] - 1

    def tile(idx_seg, rid_seg):
        with jax.named_scope(_SCORES):
            d, m, den, q = jnp.split(p_full[idx_seg], 4, axis=2)
            z = s_pad[rid_seg][:, None, :] + d           # [r, w, K]
            valid = (idx_seg != dummy)[:, :, None]
        with jax.named_scope(_STATS):
            # the dummy slot's statistics are 0: keep it out of the
            # division
            alpha = jnp.where(
                valid, jnp.exp(jax.nn.leaky_relu(z, neg_slope) - m), 0.0
            ) / jnp.where(valid, den, 1.0)
        with jax.named_scope(_GATHER):
            g = g_full[idx_seg].reshape(*idx_seg.shape, K, F // K)
            h_bar = jnp.einsum("rwk,rwkd->rkd", alpha.astype(g.dtype),
                               g).reshape(idx_seg.shape[0], F)
            own = x_pad[rid_seg].reshape(idx_seg.shape[0], K, F // K)
            u = jnp.einsum("rwkd,rkd->rwk", g, own,
                           preferred_element_type=jnp.float32)
        with jax.named_scope(_SCORES):
            s_bar = (alpha * (u - q)
                     * jnp.where(z >= 0, 1.0, neg_slope)).sum(axis=1)
        return h_bar, s_bar

    return tile


def _lane_head_width(K: int, dh: int) -> int:
    """The head width the hand-written passes run their tiles at:
    ``dh`` rounded up to the 128 lanes of a vector register, where the
    K heads then fill no more lane tiles than the K*dh-wide row already
    does in memory (3 x 250 -> 3 x 256 = 768 = 750 rounded up) — the
    gather moves the same bytes and the ``[r, w, K, dh]`` view of the
    gathered tile is free; at 250 it is a relayout of the whole tile
    every scan step.  Else ``dh`` (one head, or 8 x 8, where padding
    the heads apart would gather 16x the row)."""
    dhp = -(-dh // 128) * 128
    return dhp if K > 1 and K * dhp <= -(-K * dh // 128) * 128 else dh


def _pad_heads(a: jax.Array, K: int, dhp: int) -> jax.Array:
    """``[N, K*dh] -> [N, K*dhp]``, zeros past each head's ``dh``."""
    dh = a.shape[1] // K
    if dhp == dh:
        return a
    return jnp.pad(a.reshape(-1, K, dh), ((0, 0), (0, 0), (0, dhp - dh))
                   ).reshape(-1, K * dhp)


def _unpad_heads(a: jax.Array, K: int, dh: int) -> jax.Array:
    dhp = a.shape[1] // K
    if dhp == dh:
        return a
    return a.reshape(-1, K, dhp)[:, :, :dh].reshape(-1, K * dh)


def _bucket_rows(tile, ell_idx, ell_row_id, num_rows, dummy, unit,
                 budget_elems, remat=False):
    """``tile(idx_seg, rid_seg)`` -> a tuple of ``[rows, .]`` arrays,
    run over every bucket; returns, per output, the buckets' rows in
    bucket order.  A bucket past ``budget_elems`` (``unit`` elements a
    (row, width) slot) is row-segmented under ``lax.scan``; every
    segment writes its own rows of the stacked outputs and the scan
    carries nothing, so the segment count costs loop steps only.
    ``remat`` checkpoints each step, for a scan autodiff goes
    through."""
    cols = None
    for idx, rid in zip(ell_idx, ell_row_id):
        R, W = idx.shape
        if R * W * unit <= budget_elems:
            outs = tile(idx, rid)
        else:
            segs = -(-R * W * unit // budget_elems)
            seg_rows = -(-R // segs)
            Rp = seg_rows * segs
            idx_p = jnp.concatenate(
                [idx, jnp.full((Rp - R, W), dummy, dtype=idx.dtype)],
                axis=0)
            rid_p = jnp.concatenate(
                [rid, jnp.full((Rp - R,), num_rows, dtype=rid.dtype)],
                axis=0)
            step = jax.checkpoint(tile) if remat else tile

            def body(_, ch):
                return None, step(*ch)

            _, stacked = lax.scan(body, None,
                                  (idx_p.reshape(segs, seg_rows, W),
                                   rid_p.reshape(segs, seg_rows)))
            outs = tuple(o.reshape(Rp, o.shape[-1])[:R] for o in stacked)
        cols = cols or tuple([] for _ in outs)
        for col, o in zip(cols, outs):
            col.append(o)
    return cols


def _in_row_order(parts, ell_row_pos):
    """Bucket-order rows -> vertex order; a row in no bucket reads the
    appended zero row."""
    zero = jnp.zeros((1, parts[0].shape[1]), dtype=parts[0].dtype)
    return jnp.concatenate(list(parts) + [zero], axis=0)[ell_row_pos]


def _slot_elems(full, s_full):
    """Elements a (row, width) slot holds while a tile is in flight:
    the feature row and three fp32 score tensors a head."""
    return full.shape[1] + 3 * s_full.shape[1]


def gat_aggregate_ell(full: jax.Array, s_full: jax.Array,
                      d_local: jax.Array, ell_idx, ell_row_id,
                      ell_row_pos: jax.Array, num_rows: int,
                      neg_slope: float = 0.2,
                      budget_elems: int = 1 << 24) -> jax.Array:
    """Attention-weighted neighbor aggregation over ELL buckets,
    multi-head: K heads attend independently over the same
    neighborhood and their outputs concatenate (the GAT paper's
    concat form; K == 1 is single-head).  The forward of every path,
    and the whole of the directed one: autodiff goes through it.

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

    Each scan step is rematerialized: WITHOUT it, autodiff saves every
    step's [seg_rows, W, F] feature gather as a stacked scan residual —
    [segs, seg_rows, W, F] = 18.5 GiB at products scale (observed OOM,
    v5e 2026-07-30).  Its transpose also carries one whole [G+1, .]
    cotangent per closed-over array and scatter-adds into it once a
    segment — what :func:`gat_ell_backward` avoids on a symmetric
    graph.

    NOTE (compile size): every bucket past the budget emits its own
    scan — at products scale (lognormal degrees -> ~18 width buckets)
    the unrolled HLO pushed remote compile past 40 min.  Large-graph
    attention therefore routes through gat_aggregate_flat8 (ONE
    uniform scan shape) — see resolve_attention_impl.
    """
    (outs,) = _bucket_rows(
        _forward_tile(full, s_full, d_local, neg_slope), ell_idx,
        ell_row_id, num_rows, full.shape[0] - 1,
        _slot_elems(full, s_full), budget_elems, remat=True)
    with jax.named_scope(_GATHER):
        return _in_row_order(outs, ell_row_pos)


def gat_ell_forward(full: jax.Array, s_full: jax.Array,
                    d_local: jax.Array, ell_idx, ell_row_id,
                    ell_row_pos: jax.Array, num_rows: int,
                    neg_slope: float = 0.2,
                    budget_elems: int = 1 << 24):
    """:func:`gat_aggregate_ell` as the symmetric VJP's forward rule:
    the same tiles, and from each, while it is in hand, what the
    backward needs per vertex — ``(out [num_rows, F], c [num_rows, F]
    fp32, stats [num_rows, 2K] fp32)`` (:func:`_forward_tile`).  Nothing is
    differentiated through, so no step is rematerialized."""
    K = s_full.shape[1]
    dh = full.shape[1] // K
    with jax.named_scope(_GATHER):
        wide = _pad_heads(full, K, _lane_head_width(K, dh))
    outs, cs, stats = _bucket_rows(
        _forward_tile(wide, s_full, d_local, neg_slope, residuals=True),
        ell_idx, ell_row_id, num_rows, full.shape[0] - 1,
        _slot_elems(full, s_full), budget_elems)
    with jax.named_scope(_GATHER):
        out = _unpad_heads(_in_row_order(outs, ell_row_pos), K, dh)
        c = _unpad_heads(_in_row_order(cs, ell_row_pos), K, dh)
    with jax.named_scope(_STATS):
        return out, c, _in_row_order(stats, ell_row_pos)


def gat_ell_backward(x: jax.Array, a_src: jax.Array, a_dst: jax.Array,
                     out: jax.Array, c: jax.Array, stats: jax.Array,
                     g: jax.Array, halo, ell_idx, ell_row_id,
                     ell_row_pos: jax.Array, num_rows: int,
                     neg_slope: float = 0.2,
                     budget_elems: int = 1 << 24):
    """Attention's backward on a SYMMETRIC graph as scatter-free
    passes over the forward's own tables — the reference's design
    (backward = the same kernel on the transposed graph,
    ``scattergather_kernel.cu:160-170``) carried over to attention:
    edge ``i <- j``'s weight is a function of per-vertex quantities
    (``s_j``; ``d_i``, row ``i``'s max and denominator), so row ``j``
    can recompute it for every row it feeds from a gather of those,
    and no cotangent is scattered (:func:`_transposed_tile`).  The
    destination scores' gradient is per vertex, from the forward
    rule's ``c``.

    x [num_rows, F], a_src / a_dst [K, dh]: the op's inputs; out, c,
    stats: :func:`gat_ell_forward`'s; g: the cotangent of ``out``;
    ``halo``: GraphContext.gathered_with_zero, through which ``g``
    and the packed per-vertex scalars reach the rows of other
    partitions as the forward's features did.  Returns the cotangents
    of ``(x, a_src, a_dst)``.  False on a directed graph: the rows
    ``j`` feeds are then not the rows in ``j``'s own bucket row."""
    K, dh = a_src.shape
    F = K * dh
    f32 = jnp.float32
    with jax.named_scope(_SCORES):
        xr = x.reshape(num_rows, K, dh)
        gr = g.reshape(num_rows, K, dh)
        s = jnp.einsum("vkd,kd->vk", xr, a_src.astype(x.dtype),
                       preferred_element_type=f32)
        d = jnp.einsum("vkd,kd->vk", xr, a_dst.astype(x.dtype),
                       preferred_element_type=f32)
        q = jnp.einsum("vkd,vkd->vk", gr, out.reshape(num_rows, K, dh),
                       preferred_element_type=f32)
        d_bar = jnp.einsum("vkd,vkd->vk", gr.astype(f32),
                           c.reshape(num_rows, K, dh))
        packed = jnp.concatenate([d, stats, q], axis=1)  # [rows, 4K]
        s_pad = jnp.concatenate([s, jnp.zeros((1, K), dtype=f32)])
        p_full = halo(packed)
    with jax.named_scope(_GATHER):
        dhp = _lane_head_width(K, dh)
        g_full = halo(_pad_heads(g, K, dhp))
        x_pad = _pad_heads(
            jnp.concatenate([x, jnp.zeros((1, F), dtype=x.dtype)]), K, dhp)
        # the scans' own plumbing books to this phase; a tile's
        # operations to the phase they name
        h_parts, s_parts = _bucket_rows(
            _transposed_tile(g_full, p_full, s_pad, x_pad, neg_slope),
            ell_idx, ell_row_id, num_rows, g_full.shape[0] - 1,
            _slot_elems(g, s_pad), budget_elems)
        h_bar = _unpad_heads(_in_row_order(h_parts, ell_row_pos), K, dh)
    with jax.named_scope(_SCORES):
        s_bar = _in_row_order(s_parts, ell_row_pos)
        through_scores = (s_bar[:, :, None] * a_src.astype(f32)
                          + d_bar[:, :, None] * a_dst.astype(f32))
        x_bar = h_bar + through_scores.reshape(num_rows, F).astype(x.dtype)
        a_src_bar = jnp.einsum("vk,vkd->kd", s_bar, xr,
                               preferred_element_type=f32)
        a_dst_bar = jnp.einsum("vk,vkd->kd", d_bar, xr,
                               preferred_element_type=f32)
    return (x_bar, a_src_bar.astype(a_src.dtype),
            a_dst_bar.astype(a_dst.dtype))


_U32 = jnp.uint32


def _fmix32(h):
    """MurmurHash3's 32-bit finalizer: every input bit moves every
    output bit."""
    h = h ^ (h >> 16)
    h = h * _U32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * _U32(0xC2B2AE35)
    return h ^ (h >> 16)


def edge_keep_scale(dst, src, heads: int, seed, rate: float):
    """``D_ij^h / (1 - rate)``, float32, ``D ~ Bernoulli(1 - rate)``,
    for the edge ``dst <- src`` (broadcastable int32 arrays of global
    vertex ids) and each of ``heads`` heads on a new trailing axis.  A
    function of the edge, the head and ``seed`` (``uint32[2]``, drawn
    from the step's key) alone — not of where the edge sits in a table
    — so the forward and the pass over the transposed table draw the
    same mask."""
    h = _fmix32(seed[0] ^ (dst.astype(_U32) * _U32(0x9E3779B1)))
    h = _fmix32(h ^ (src.astype(_U32) * _U32(0x85EBCA77)))
    head = jnp.arange(heads, dtype=_U32) * _U32(0xC2B2AE3D)
    h = _fmix32(h[..., None] ^ seed[1] ^ head)
    cut = _U32(min(int(round(rate * 2.0 ** 32)), 2 ** 32 - 1))
    return jnp.where(h >= cut, jnp.float32(1.0 / (1.0 - rate)),
                     jnp.float32(0.0))


def _heads(a, d):
    """The head slices ``[..., d]`` of ``a`` ``[..., K*d]``, a list.
    Lane slices: at a head width of 128 each is a whole vreg column, and
    no ``[..., K, d]`` view exists whose second-minor axis (the head
    count) the chip's ``(8, 128)`` tiles would pad — XLA lays such a
    view out anew in HBM, a copy of the whole gathered tile."""
    return [a[..., h * d:(h + 1) * d] for h in range(a.shape[-1] // d)]


def _per_row(slots, rows):
    """``[r, w, K]`` float32: per head the dot product of each slot's
    ``[r, w, d]`` slice with its row's ``[r, d]`` slice."""
    return jnp.stack([jnp.einsum("rwd,rd->rw", a, b,
                                 preferred_element_type=jnp.float32)
                      for a, b in zip(slots, rows)], axis=-1)


def _over_slots(weights, slots):
    """``[r, K*d]`` float32: per head the sum over the width axis of the
    ``[r, w, K]`` weights times the slots' ``[r, w, d]`` slice, the
    weights rounded to the slices' dtype."""
    return jnp.concatenate(
        [jnp.einsum("rw,rwd->rd", weights[..., h].astype(a.dtype), a,
                    preferred_element_type=jnp.float32)
         for h, a in enumerate(slots)], axis=-1)


def _dot_forward_tile(kv_full, q_pad, d, scale, keep, residuals=False):
    """``tile(idx_seg, rid_seg)`` of the dot-product attention forward:
    one gather of the ``[k | v]`` rows a slot, the ``K`` scores against
    the row's own query, the masked softmax and the weighted sum in
    float32, and the per-edge dropout ``keep(dst, src)`` (None: none).
    Returns ``(m [r, F] fp32,)``, with ``residuals`` also the row
    statistics ``[r, 2K]`` fp32 (row max, denominator)."""
    dummy = kv_full.shape[0] - 1
    F = q_pad.shape[1]

    def tile(idx_seg, rid_seg):
        with jax.named_scope(_SCORES):
            kv = kv_full[idx_seg]                       # [r, w, 2F]
            s = _per_row(_heads(kv[..., :F], d),
                         _heads(q_pad[rid_seg], d)) * scale
            valid = (idx_seg != dummy)[:, :, None]
            s = jnp.where(valid, s, -jnp.inf)
        with jax.named_scope(_STATS):
            m = jnp.max(s, axis=1, keepdims=True)
            m = jnp.where(jnp.isfinite(m), m, 0.0)
            e = jnp.where(valid, jnp.exp(s - m), 0.0)
            den = jnp.maximum(e.sum(axis=1, keepdims=True), 1e-20)
        with jax.named_scope(_GATHER):
            t = e / den
            if keep is not None:
                t = t * keep(rid_seg[:, None], idx_seg)
            out = _over_slots(t, _heads(kv[..., F:], d))
        if not residuals:
            return (out,)
        with jax.named_scope(_STATS):
            return out, jnp.concatenate([m[:, 0], den[:, 0]], axis=1)

    return tile


def _dot_query_tile(kv_full, q_pad, g_pad, st_pad, d, scale, keep):
    """Pass A of the backward, ``tile(idx_seg, rid_seg)``: at row ``i``
    over its own slots ``j``, with ``alpha_ij`` recomputed from the
    row's kept statistics,

        ds_ij = alpha_ij (D_ij / (1 - p) G_i . v_j - rho_i)
        q_bar_i = sum_j ds_ij k_j / sqrt(d)

    ``g_pad`` ``[num_rows+1, F]`` the cotangent of ``m``, ``st_pad``
    ``[num_rows+1, 3K]`` fp32 (row max, denominator, ``rho``), a zero
    row last in each."""
    dummy = kv_full.shape[0] - 1
    F = q_pad.shape[1]

    def tile(idx_seg, rid_seg):
        with jax.named_scope(_SCORES):
            kv = kv_full[idx_seg]
            k = _heads(kv[..., :F], d)
            s = _per_row(k, _heads(q_pad[rid_seg], d)) * scale
            valid = (idx_seg != dummy)[:, :, None]
            mx, den, rho = jnp.split(st_pad[rid_seg], 3, axis=1)
        with jax.named_scope(_STATS):
            alpha = jnp.where(valid, jnp.exp(s - mx[:, None, :]), 0.0
                              ) / jnp.maximum(den, 1e-20)[:, None, :]
        with jax.named_scope(_GATHER):
            g = g_pad[rid_seg].astype(kv.dtype)
            gv = _per_row(_heads(kv[..., F:], d), _heads(g, d))
            if keep is not None:
                gv = gv * keep(rid_seg[:, None], idx_seg)
            ds = alpha * (gv - rho[:, None, :])
            return (_over_slots(ds, k) * scale,)

    return tile


def _dot_key_tile(pg_full, st_full, kv_pad, d, scale, keep):
    """Pass B of the backward, ``tile(idx_seg, rid_seg)``: at row ``j``
    over its own slots ``i`` — on a symmetric graph the rows ``j``
    feeds — with ``alpha_ij`` and ``D_ij`` recomputed from row ``i``'s
    gathered query and statistics,

        v_bar_j = sum_i t_ij G_i
        k_bar_j = sum_i ds_ij q_i / sqrt(d)

    ``pg_full`` ``[G+1, 2F]`` the rows ``[q | G]`` through the halo,
    ``st_full`` ``[G+1, 3K]`` fp32 their (row max, denominator,
    ``rho``), ``kv_pad`` ``[num_rows+1, 2F]`` the rows' own ``[k |
    v]``.  Returns ``([k_bar | v_bar] [r, 2F] fp32,)``."""
    dummy = pg_full.shape[0] - 1
    F = pg_full.shape[1] // 2

    def tile(idx_seg, rid_seg):
        with jax.named_scope(_SCORES):
            pg = pg_full[idx_seg]                       # [r, w, 2F]
            q = _heads(pg[..., :F], d)
            g = _heads(pg[..., F:], d)
            own = kv_pad[rid_seg].astype(pg.dtype)
            s = _per_row(q, _heads(own[:, :F], d)) * scale
            mx, den, rho = jnp.split(st_full[idx_seg], 3, axis=2)
            valid = (idx_seg != dummy)[:, :, None]
        with jax.named_scope(_STATS):
            # the dummy slot's statistics are 0: keep it out of the
            # division
            alpha = jnp.where(valid, jnp.exp(s - mx), 0.0) / jnp.where(
                valid, jnp.maximum(den, 1e-20), 1.0)
        with jax.named_scope(_GATHER):
            drop = (keep(idx_seg, rid_seg[:, None]) if keep is not None
                    else 1.0)
            v_bar = _over_slots(alpha * drop, g)
            gv = _per_row(g, _heads(own[:, F:], d))
            ds = alpha * (drop * gv - rho)
            return (jnp.concatenate([_over_slots(ds, q) * scale, v_bar],
                                    axis=1),)

    return tile


def _dot_slot_elems(kv_width: int, heads: int) -> int:
    """Elements a (row, width) slot holds while a dot-product tile is
    in flight: the gathered ``[k | v]`` (or ``[q | G]``) row and four
    fp32 score tensors a head."""
    return kv_width + 4 * heads


def _padded_rows(a: jax.Array) -> jax.Array:
    """``a`` with a zero row appended: what a padding bucket row (row id
    ``num_rows``) reads."""
    return jnp.concatenate([a, jnp.zeros((1,) + a.shape[1:], a.dtype)])


def dot_ell_forward(kv_full: jax.Array, q: jax.Array, head_width: int,
                    ell_idx, ell_row_id, ell_row_pos: jax.Array,
                    num_rows: int, keep=None, residuals: bool = False,
                    budget_elems: int = 1 << 24):
    """Dot-product attention over the ELL buckets, K heads side by
    side: ``m_i^h = sum_j t_ij^h v_j^h`` with ``t = softmax_j(q_i^h .
    k_j^h / sqrt(d)) * D_ij^h / (1 - p)``.  ``kv_full`` ``[G+1, 2F]``
    the ``[k | v]`` rows through the halo with a zero row last; ``q``
    ``[num_rows, F]`` the rows' own queries; ``keep(dst, src)`` the
    dropout multiplier (:func:`edge_keep_scale`; None: none).  Returns
    ``m`` ``[num_rows, F]`` fp32, and with ``residuals`` the row
    statistics ``[num_rows, 2K]`` fp32 beside it.  Rows with no stored
    edge read 0.  Nothing is differentiated through: the gradient is
    :func:`dot_ell_backward`."""
    F = q.shape[1]
    K = F // head_width
    parts = _bucket_rows(
        _dot_forward_tile(kv_full, _padded_rows(q), head_width,
                          head_width ** -0.5, keep, residuals),
        ell_idx, ell_row_id, num_rows, kv_full.shape[0] - 1,
        _dot_slot_elems(kv_full.shape[1], K), budget_elems)
    with jax.named_scope(_GATHER):
        m = _in_row_order(parts[0], ell_row_pos)
    if not residuals:
        return m
    with jax.named_scope(_STATS):
        return m, _in_row_order(parts[1], ell_row_pos)


def dot_ell_backward(q: jax.Array, kv: jax.Array, m: jax.Array,
                     stats: jax.Array, g: jax.Array, head_width: int,
                     halo, ell_idx, ell_row_id, ell_row_pos: jax.Array,
                     num_rows: int, keep=None,
                     budget_elems: int = 1 << 24):
    """The backward of :func:`dot_ell_forward` on a SYMMETRIC graph as
    two scatter-free passes over the forward's own tables: pass A
    (``dq``) at each row over its own slots, gathering ``[k | v]``;
    pass B (``dk``, ``dv``) at each row over the rows it feeds,
    gathering ``[q | G]`` and their (row max, denominator, ``rho``)
    through ``halo`` (``GraphContext.gathered_with_zero``).  With
    ``rho_i = G_i . m_i`` per head — ``sum_j t_ij G_i . v_j``, no pass
    needed — and ``ds_ij = alpha_ij (D_ij / (1 - p) G_i . v_j -
    rho_i)``:

        q_bar_i = sum_j ds_ij k_j / sqrt(d)             pass A
        k_bar_j = sum_i ds_ij q_i / sqrt(d)             pass B
        v_bar_j = sum_i t_ij G_i                        pass B

    ``q`` ``[num_rows, F]``, ``kv`` ``[num_rows, 2F]`` the op's inputs;
    ``m``, ``stats`` the forward's; ``g`` the cotangent of ``m``.
    Returns ``(q_bar, kv_bar)`` in the inputs' dtypes.  Wrong on a
    directed graph: the rows ``j`` feeds are then not the rows in
    ``j``'s own bucket row."""
    F = q.shape[1]
    K = F // head_width
    d = head_width
    f32 = jnp.float32
    with jax.named_scope(_SCORES):
        rho = jnp.einsum("vkd,vkd->vk", g.reshape(num_rows, K, d),
                         m.reshape(num_rows, K, d),
                         preferred_element_type=f32)
        st = jnp.concatenate([stats, rho], axis=1)      # [rows, 3K]
        st_full = halo(st)
    with jax.named_scope(_GATHER):
        kv_full = halo(kv)
        pg_full = halo(jnp.concatenate([q, g.astype(q.dtype)], axis=1))
        tables = (ell_idx, ell_row_id, num_rows)
        (qa,) = _bucket_rows(
            _dot_query_tile(kv_full, _padded_rows(q), _padded_rows(g),
                            _padded_rows(st), d, d ** -0.5, keep),
            *tables, kv_full.shape[0] - 1,
            _dot_slot_elems(kv_full.shape[1], K), budget_elems)
        (kb,) = _bucket_rows(
            _dot_key_tile(pg_full, st_full, _padded_rows(kv), d,
                          d ** -0.5, keep),
            *tables, pg_full.shape[0] - 1,
            _dot_slot_elems(pg_full.shape[1], K), budget_elems)
        q_bar = _in_row_order(qa, ell_row_pos)
        kv_bar = _in_row_order(kb, ell_row_pos)
    return q_bar.astype(q.dtype), kv_bar.astype(kv.dtype)


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

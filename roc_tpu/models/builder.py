"""Model builder: the reference's ``Model`` API rebuilt functionally.

The reference ``Model`` class (``gnn.h:162-203``) exposes
``dropout / linear / scatter_gather / indegree_norm / relu / sigmoid /
add / softmax_cross_entropy`` which append ``GnnOp*`` to a layer list
(e.g. ``linear.cc:20-29``); ``forward()`` walks the list and
``backward()`` walks it in reverse with hand-written gradients
(``gnn.cc:696-716``).

Here the same builder API records a static op list; :meth:`Model.apply`
interprets it inside a traced JAX function, so XLA sees one fused program
and ``jax.grad`` replaces the reference's manual autodiff driver
(including the shared-input gradient-accumulation bookkeeping of
``gnn.cc:705-713`` — JAX accumulates fanout cotangents automatically).

Graph access is abstracted behind :class:`GraphContext` so the same model
runs single-device (identity feature gather) and under ``shard_map``
(ICI ``all_gather`` feature halo — the reference's whole-region input
requirement, ``scattergather.cc:70-72``).
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ell import LANE_WIDTH, agg_lane_width
from ..core.memory import remat_segments
from ..models.tables import (EllTables, FlatTables, GraphArrays, RingTables,
                           Tables)
from ..core.relations import (AGGREGATE_FIRST, LINEAR_FIRST, ORDER_PASSES,
                              TRANSFORM_FIRST)
from ..obs.scopes import (ALLREDUCE_SCOPE, ATTN_GATE_SCOPE,
                          ATTN_SCORES_SCOPE, BN_PARAM_PREFIX, EMBED_SCOPE,
                          HALO_SCOPE, LN_PARAM_PREFIX, LOSS_SCOPE,
                          RECOMPUTE_SCOPE, op_scope)
from ..ops import dense, softagg
from ..parallel import PARTS_AXIS
from ..ops.aggregate import (gather_sum_form, gather_sum_slots,
                             scan_seg_sum, scan_window_rows,
                             seg_sum_updates)
from ..ops.dense import AC_MODE_NONE, AC_MODE_RELU, AC_MODE_SIGMOID
from ..ops.loss import masked_softmax_cross_entropy
from ..ops.norm import (BN_EPS, BN_MOMENTUM, LN_EPS, batch_norm_eval,
                        batch_norm_train, indegree_norm, layer_norm,
                        running_update)

# AggrType mirror (gnn.h:75-80); the reference declares SUM/AVG/MAX/MIN
# but implements only SUM.  Here SUM and AVG ride the symmetric-vjp CSR
# path; MAX/MIN use exact autodiff (nonlinear, so the reference's
# kernel-reuse trick does not apply; MIN = -MAX(-x)).
AGGR_SUM = "sum"
AGGR_AVG = "avg"
AGGR_MAX = "max"
AGGR_MIN = "min"

# a batch_norm op's entries of the parameter dict, ``bn_<n>_<suffix>``:
# what Adam trains, and the state no optimizer sees
BN_TRAINED = ("scale", "shift")
BN_STATE = ("mean", "var")
# a layer_norm op's entries, ``ln_<n>_<suffix>`` (float32, trained)
LN_TRAINED = ("scale", "shift")
# the key stream of the dot-product attention's edge dropout, apart
# from the dropout ops' own (which fold in their ordinal)
EDGE_DROPOUT_STREAM = 0x65646765

# the softmax aggregation's backward is the pass over the TRANSPOSED
# table; on a symmetric graph that is the forward's own table, and no
# transposed sum table is built for a directed one (ROADMAP R2(c))
SOFT_DIRECTED_REFUSAL = (
    "the softmax-weighted aggregation (soft_aggregate; --model "
    "deepergcn) needs a symmetric stored graph: its hand-written "
    "backward gathers g / den over the transposed graph, and no "
    "transposed sum table is built for a directed one")

# the dot-product attention's gradient is two passes over the bucket
# tables; the one over the transposed graph is the forward's own table
# on a symmetric graph only (ROADMAP R2)
TFATTN_DIRECTED_REFUSAL = (
    "the dot-product attention (transformer_attention; --model gtrans) "
    "needs a symmetric stored graph: its hand-written backward's pass "
    "for the keys and values walks each row's own bucket row as the "
    "rows it feeds, and no by-source table is built for a directed one")
TFATTN_FLAT8_REFUSAL = (
    "the dot-product attention (transformer_attention; --model gtrans) "
    "runs on the bucketed ELL tables alone: the uniform width-8 flat "
    "layout (aggr_impl='attn_flat8', where attention routes at >= 20M "
    "stored edges) has no dot-product score and no two-pass backward")
TFATTN_PARTITION_REFUSAL = (
    "the dot-product attention (transformer_attention; --model gtrans) "
    "runs on one partition: its per-edge dropout mask hashes global "
    "vertex ids, which a partition's padded row space does not carry "
    "(--parts 1)")


@dataclass
class GraphContext:
    """Per-device view of the (partitioned) graph inside a step function.

    tables: one layout's tables (``models/tables.py``): source ids in
      *row-coordinate space* — indices into the feature matrix
      produced by ``gather_features``, with the dummy zero row at index
      ``gathered_rows`` — and the rows they sum into.
    in_degree: int32 [num_rows] real in-degrees of local rows.
    num_rows: static local row count (padded).
    gathered_rows: static row count of the gathered feature matrix
      (== num_rows single-device; == parts * num_rows under shard_map).
    gather_features: the halo exchange — identity single-device,
      ``lax.all_gather`` over the mesh axis in the distributed step.
    psum: metric/loss reduction across shards (identity single-device).
    """

    tables: Tables
    in_degree: jax.Array
    num_rows: int
    gathered_rows: int
    gather_features: Callable[[jax.Array], jax.Array] = lambda x: x
    psum: Callable[[Any], Any] = lambda x: x
    # the resolved layout's name: what the plan lines report and the
    # lane rule (core/ell.py agg_lane_width) reads; the ops read the
    # tables themselves
    aggr_impl: str = "segment"
    symmetric: bool = True
    # halo exchange mode: "gather" = one-shot all_gather of the full
    # feature matrix (the reference's whole-region requirement);
    # "ring" = ppermute rotation overlapping per-shard aggregation
    # (parallel/ring.py, the tables a RingTables) — O(V/P * F) peak
    # memory instead of O(V * F)
    halo: str = "gather"
    # double-buffered ring schedule (ppermute issued before the local
    # scatter-accumulate, parallel/ring.py ring_aggregate): identical
    # numerics either way; False keeps the strictly sequential hop
    # order for measurement/debug (TrainConfig.ring_overlap)
    ring_overlap: bool = True
    # Chunked output head (TrainConfig.head_chunk, resolved by
    # train/trainer.resolve_head_chunk): when > 0, the LAST linear
    # (the classification head) is evaluated as a lax.scan over
    # head_chunk-row blocks (ops/dense.py linear_chunked) so the
    # head's compiled matmul shape is [head_chunk, C] — independent of
    # V_p — instead of the full [V_p, C] width.  0 = the plain
    # full-width matmul.  Values and dX are bit-identical either way;
    # dW sums the row axis blockwise (fp32 roundoff-level difference,
    # ops/dense.py linear_chunked).
    head_chunk: int = 0
    axis_name: str = PARTS_AXIS
    # What an op that reduces over the vertex axis (``batch_norm``; the
    # softmax aggregation's shift) has to know about the rows:
    # ``real_rows`` the traced int32 count of this partition's real
    # rows, which come first (None: every row is real — one device has
    # no padding), ``total_rows`` the static count of real rows over
    # all partitions (0: ``num_rows``).
    real_rows: Optional[jax.Array] = None
    total_rows: int = 0

    @property
    def partitioned(self) -> bool:
        """Whether the rows are one partition's of several (the
        gathered matrix is taller than the local one)."""
        return self.gathered_rows != self.num_rows

    @property
    def counted_rows(self) -> int:
        """Real rows over all partitions: what a moment divides by."""
        return self.total_rows or self.num_rows

    def valid_rows(self) -> Optional[jax.Array]:
        """``[num_rows]`` bool, True on the real rows; None where every
        row is real."""
        if self.real_rows is None:
            return None
        return jnp.arange(self.num_rows, dtype=jnp.int32) < self.real_rows

    def batch_norm(self, x: jax.Array, scale: jax.Array,
                   shift: jax.Array):
        """Training-mode batch normalization over the real vertex rows
        of every partition (``ops/norm.py batch_norm_train``): ``(y,
        mean, var)``.  Across partitions the two moments are one
        ``psum`` of ``[2, F]`` under ``roc.allreduce``."""
        return batch_norm_train(
            x, scale, shift, count=self.counted_rows,
            valid=self.valid_rows(),
            psum=self.psum if self.partitioned else None)

    def agg_window(self, ops=(), edges=None,
                   compute=jnp.float32) -> dict:
        """How far the chunk scan's destination window engaged — the
        run manifest's ``resolved`` carries it (obs/manifest.py):
        rows a chunk step reads and writes per section
        (``scan_window_rows`` of the table's ``win_rows``) against the
        carry's height.  Empty for the layouts that scan no carry.
        Beside it ``agg_lane_pad``: one ``[op, F, Fp]`` entry per
        sum-aggregating op of ``ops`` (``Model._ops``; SUM and AVG
        ``scatter_gather``, ``fused_aggregate``) — the width ``F`` the
        model gives the op and the width ``Fp`` its scan runs at
        (``core/ell.py agg_lane_width``; ``Fp == F`` where the rule
        did not engage).  And how far ``core/ell.py fit_chunks``
        engaged: ``agg_chunk_rows``, one ``[n_chunks, seg_rows]`` per
        section of the sum scan's index tables, and ``agg_slot_fill``,
        the graph's ``edges`` stored edges over the slots a pass
        gathers (None where the tables hold another edge set:
        ``bdense``'s residual).  And how far the scan's segmented sum
        engaged (``ops/aggregate.py scan_seg_sum``), one entry per
        table the sum scan walks — the sections, or a typed graph's
        relation passes: ``agg_seg_sum``, the ``[T, B]`` a chunk step
        sums its partials at on the MXU, or None where it scatters
        them one by one or its kernel adds its own sums (a ``"fused"``
        table, below), and ``agg_carry_updates``, ``[before, after]``:
        a pass's sub-rows, and the rows it still adds into the carry
        outside the kernel — 0 on a ``"fused"`` table, the slab rows
        where the segmented sum runs, the sub-rows where the serial
        scatter does (``ops/aggregate.py seg_sum_updates``).  Both are
        read at the widest sum op's width (a narrower op may pick a
        taller tile of the same table).  The distributed trainer reads
        its stacked tables through a context that holds them: the
        trailing axes are read, every part's slots counted.  And how
        each of those tables' chunk steps make their partials
        (``ops/aggregate.py gather_sum_form``, read off the table's
        rows, the widest sum op's width and the ``compute`` dtype the
        table is held in): ``agg_gather_sum``, one ``[form, slots]`` a
        table — ``"fused"`` where the kernel sums a sub-row's 8 slots
        in VMEM and adds the sum into its destination row,
        ``"two_pass"`` where they go through HBM — and the slots a
        pass gathers."""
        carry = self.num_rows + 1
        # the chunk tables the sum scan walks: (idx, win_rows, bands,
        # rows of the table a scan gathers out of — the section and its
        # zero row, or the gathered matrix and its)
        scanned = self.tables.windows(self.gathered_rows)
        wins = [w for _, w, _, _ in scanned]
        tables = [t for t, _, _, _ in scanned]
        pads = [[i, op.dim, self._lane_width(op.dim)]
                for i, op in enumerate(ops)
                if op.kind == "fused_aggregate"
                or (op.kind == "scatter_gather"
                    and op.attrs["aggr"] in (AGGR_SUM, AGGR_AVG))]
        # a softmax aggregation's forward sum runs at its table's width
        pads += [[i, op.dim, self._lane_width(2 * op.dim)]
                 for i, op in enumerate(ops)
                 if op.kind == "soft_aggregate"]
        slots = sum(int(t.size) for t in tables)
        width = max((p[2] for p in pads), default=LANE_WIDTH)
        # (n_chunks, seg_rows, window, bands, table rows, sub-row
        # width) of every scanned table
        scans = [(*t.shape[-3:-1], scan_window_rows(w, carry), b, r,
                  t.shape[-1]) for t, w, b, r in scanned]
        scans += self.tables.pass_scans()
        forms = [gather_sum_form(r, width, compute)
                 for _, _, _, _, r, _ in scans]
        # a fused table's kernel adds its own sums: no segmented sum
        segs = [None if f == "fused" else scan_seg_sum(seg, w, b, width)
                for (_, seg, w, b, _, _), f in zip(scans, forms)]
        return {"agg_window_rows": [scan_window_rows(w, carry)
                                    for w in wins],
                "agg_carry_rows": carry if wins else None,
                "agg_lane_pad": pads,
                "agg_seg_sum": [s and list(s) for s in segs],
                "agg_carry_updates": [
                    seg_sum_updates(n, seg, s, f)
                    for (n, seg, *_), s, f in zip(scans, segs, forms)],
                "agg_gather_sum": [
                    gather_sum_slots(n, seg, r, width, compute, sub_w)
                    for n, seg, _, _, r, sub_w in scans],
                "agg_chunk_rows": [list(t.shape[-3:-1])
                                   for t in tables],
                "agg_slot_fill": (
                    round(edges / slots, 4) if edges and slots
                    and self.tables.holds_edges else None)}

    def attention_plan(self, ops) -> dict:
        """What each attention op of ``ops`` (``Model._ops``) runs on —
        the run manifest's ``resolved`` carries it beside
        :meth:`agg_window`: heads, head width, the layout, forward
        passes over the edge tables (the bucketed layout reduces a row
        in one; the flat layout scans once for the row max, once for
        the numerator — once a ``resolve_dh_chunk`` slice — and once
        more for the denominator when sliced), table slots a pass
        (stored edges + padding, per partition), and the height of the
        fp32 carry a scan step rewrites (None: the bucketed layout
        carries nothing).  Beside it, one ``attention_backward`` entry
        per op: the gradient rule :meth:`gat_attention` takes
        (``transposed``: the bucketed layout on a symmetric graph;
        ``autodiff`` otherwise), the passes over the edge tables its
        backward makes (autodiff recomputes each forward scan step,
        then transposes it) and the whole ``[G+1, .]`` cotangents
        those scatter-add into, once a scan step each (features,
        source and destination scores).  Empty for a model without
        attention.  The distributed trainer's context holds the stacked
        tables: the shapes' trailing axes are read.

        A dot-product attention op (``transformer_attention``) adds to
        its entry ``score: "dot"``, the lanes its forward gathers a
        slot (``gather_lanes_fwd``: the ``[k | v]`` row), its output
        width, one ``[name, lanes]`` a backward pass (``bwd_passes``)
        and ``edge_dropout`` (the rate and the mask's rule); its
        ``attention_backward`` rule is ``transposed_two_pass``."""
        from ..ops.attention import resolve_dh_chunk
        flat8 = isinstance(self.tables, FlatTables)
        ell_idx = (self.tables.idx if isinstance(self.tables, EllTables)
                   else ())
        transposed = self.symmetric and not flat8
        out, back = [], []
        for i, op in enumerate(ops):
            if op.kind == "transformer_attention":
                heads, dh = op.attrs["heads"], op.attrs["head_width"]
                lanes = 2 * heads * dh
                out.append({
                    "op": i, "heads": heads, "head_width": dh,
                    "layout": self.aggr_impl, "edge_passes": 1,
                    "padded_slots_per_pass": sum(
                        int(np.prod(a.shape[-2:])) for a in ell_idx),
                    "carry_rows": None, "score": "dot",
                    "gather_lanes_fwd": lanes, "out_width": op.dim,
                    "bwd_passes": [["dq", lanes], ["dk_dv", lanes]],
                    "edge_dropout": {
                        "p": op.attrs["rate"],
                        "mask": "hash(dst, src, head) under the "
                                "step's key"}})
                back.append({"op": i, "rule": "transposed_two_pass",
                             "edge_passes": 2, "scatters": 0})
                continue
            if op.kind != "gat":
                continue
            heads = int(op.attrs.get("heads", 1))
            dh = op.dim // heads
            if flat8:
                chunk = resolve_dh_chunk(self.num_rows, heads, dh)
                passes = 2 if chunk is None else 2 + -(-dh // chunk)
                slots = int(np.prod(self.tables.idx.shape[-3:]))
            else:
                passes = 1
                slots = sum(int(np.prod(a.shape[-2:]))
                            for a in ell_idx)
            out.append({"op": i, "heads": heads, "head_width": dh,
                        "layout": self.aggr_impl,
                        "edge_passes": passes,
                        "padded_slots_per_pass": slots,
                        "carry_rows": self.num_rows + 1 if flat8
                        else None})
            if transposed:
                back.append({"op": i, "rule": "transposed",
                             "edge_passes": 1, "scatters": 0})
                continue
            # the flat layout's row-max scan takes no gradient; its
            # denominator scan, where apart, gathers no features
            scans = passes - 1 if flat8 else passes
            apart = 1 if flat8 and scans > 1 else 0
            back.append({"op": i, "rule": "autodiff",
                         "edge_passes": 2 * scans,
                         "scatters": 3 * scans - apart})
        return ({"attention": out, "attention_backward": back}
                if out else {})

    def gathered_with_zero(self, x: jax.Array) -> jax.Array:
        """Halo exchange (under its own ``roc.halo`` scope, inside the
        aggregation's) + the appended dummy zero source row that
        padding table entries point at."""
        with jax.named_scope(HALO_SCOPE):
            full = self.gather_features(x)
        zero = jnp.zeros((1, full.shape[1]), dtype=full.dtype)
        return jnp.concatenate([full, zero], axis=0)

    def _lane_width(self, feat_width: int) -> int:
        return agg_lane_width(feat_width, self.aggr_impl, self.halo)

    def _lane_padded(self, agg, x: jax.Array) -> jax.Array:
        """``agg(x)`` at the width :func:`core.ell.agg_lane_width`
        gives: the local ``x`` zero-padded on its feature axis (before
        the halo, so the exchange moves lane-wide rows too), the result
        sliced back.  The cotangent takes the same road — the slice
        transposes to the pad — so the symmetric backward runs at the
        padded width as well.  Zeros in the spare lanes change no
        value: the real columns are bit-identical to the unpadded
        scan's."""
        F = x.shape[1]
        Fp = self._lane_width(F)
        if Fp == F:
            return agg(x)
        return agg(jnp.pad(x, ((0, 0), (0, Fp - F))))[:, :F]

    def _sum_fwd(self, x: jax.Array) -> jax.Array:
        """Halo exchange + local CSR sum: ``out = A_p @ gather(x)``."""
        return self.tables.sum(self, x)

    def aggregate_sum(self, x: jax.Array) -> jax.Array:
        """Sum aggregation with the reference's backward: for a symmetric
        global adjacency, grad_x(local) = A_p @ all_gather(cotangent) —
        the same kernel + halo exchange run on the cotangent
        (``scattergather_kernel.cu:160-170``; shard-level identity:
        row-slice_p(A^T g) = A_p g for A == A^T).  Besides parity, this
        keeps the chunk scans' backward O(chunk) memory instead of
        saving per-chunk residuals.  Set ``symmetric=False`` for exact
        autodiff through the forward (directed graphs)."""
        if not self.symmetric:
            return self._lane_padded(self._sum_fwd, x)

        @jax.custom_vjp
        def agg(x):
            return self._sum_fwd(x)

        def fwd(x):
            return agg(x), None

        def bwd(_, g):
            return (self._sum_fwd(g),)

        agg.defvjp(fwd, bwd)
        return self._lane_padded(agg, x)

    def _fused_sum_fwd(self, x: jax.Array) -> jax.Array:
        """One-pass ``D^-1/2 A D^-1/2 x`` (the GCN sandwich of
        norm -> sum-aggregate -> norm folded into the aggregation,
        Model.fuse_norm_aggregate): tables with baked per-edge weights
        read them (zero runtime normalization); otherwise ``d =
        inv_sqrt_degree(in_degree)`` is derived at trace time and the
        features are scaled once before / the output once after the
        plain sum — the same numbers as the unfused chain, still
        inside ONE op so the multiplies fuse into the aggregation's
        reads/writes."""
        from ..ops.norm import inv_sqrt_degree
        if self.tables.weighted:
            return self.tables.sum(self, x, fused=True)
        # no baked weights (or the edge-list reference): scale features
        # once per fused op, sum, scale the output
        d = inv_sqrt_degree(self.in_degree).astype(x.dtype)
        out = self._sum_fwd(x * d[:, None])
        return out * d[:, None]

    def aggregate_fused(self, x: jax.Array) -> jax.Array:
        """Fused ``S x`` with ``S = D^-1/2 A D^-1/2``.  S is symmetric
        whenever A is (diagonal scale on both sides), so the backward
        reuses the forward exactly like :meth:`aggregate_sum` —
        including the shard-level identity row-slice_p(S^T g) = S_p g.
        ``symmetric=False`` falls back to exact autodiff."""
        if not self.symmetric:
            return self._lane_padded(self._fused_sum_fwd, x)

        @jax.custom_vjp
        def agg(x):
            return self._fused_sum_fwd(x)

        def fwd(x):
            return agg(x), None

        def bwd(_, g):
            return (self._fused_sum_fwd(g),)

        agg.defvjp(fwd, bwd)
        return self._lane_padded(agg, x)

    def aggregate(self, x: jax.Array, aggr: str = AGGR_SUM) -> jax.Array:
        if aggr == AGGR_SUM:
            return self.aggregate_sum(x)
        if aggr == AGGR_AVG:
            s = self.aggregate_sum(x)
            deg = jnp.maximum(self.in_degree.astype(s.dtype), 1.0)
            return s / deg[:, None]
        if aggr == AGGR_MAX:
            return self._max_fwd(x)
        if aggr == AGGR_MIN:
            return -self._max_fwd(-x)
        raise ValueError(f"unknown aggregator: {aggr}")

    def soft_plan(self, ops=(), compute=jnp.float32) -> dict:
        """What the softmax-weighted aggregations of ``ops`` run as,
        for the run manifest's ``resolved`` beside :meth:`agg_window`:
        the ops, the temperature, the lanes a forward / backward pass
        gathers (numerator and denominator side by side forward), the
        dtype the gathered table is stored in (the compute dtype) and
        what the hand-written rule keeps.
        Empty for a model without such an op."""
        soft = [(i, op) for i, op in enumerate(ops)
                if op.kind == "soft_aggregate"]
        if not soft:
            return {}
        width = max(op.dim for _, op in soft)
        return {"soft_aggregate": {
            "ops": [i for i, _ in soft], "count": len(soft),
            "t": soft[0][1].attrs["t"], "eps": soft[0][1].attrs["eps"],
            "width": width,
            "gather_lanes_fwd": self._lane_width(2 * width),
            "gather_lanes_bwd": self._lane_width(width),
            "passes_fwd": 1, "passes_bwd": 1,
            "e_dtype": jnp.dtype(compute).name,
            "keeps": ["input", "shift[F] float32", "den float32"],
            "rule": "detached weights, transposed pass"}}

    def soft_aggregate(self, x: jax.Array, t: float,
                       eps: float) -> jax.Array:
        """``z + sum_u sg(w_vu) m_u`` — GENConv's ``softmax_sg``
        aggregation and its self term (``ops/softagg.py`` has the
        equations): the forward ONE pass of the sum aggregation
        (:meth:`_sum_fwd`: whatever layout and halo the graph resolved
        to) over the ``[rows, 2F]`` table ``[e * m, e]`` and a
        division, the backward the published rule — the weights carry
        no gradient — as ONE pass of the same sum over ``g / den`` and
        a product with ``e``.  That rule is NOT the derivative of the
        forward (autodiff would differentiate the softmax), and it is
        the pass over the transposed graph: the stored graph must be
        symmetric."""
        if not self.symmetric:
            raise NotImplementedError(SOFT_DIRECTED_REFUSAL)
        F = x.shape[1]
        valid = self.valid_rows()

        def small_gather(row):
            # the halo's all-gather over one [1, F] row a partition:
            # a collective that is neither halo nor gradient
            with jax.named_scope(ALLREDUCE_SCOPE):
                return self.gather_features(row)

        def lanes(a):
            return self._lane_padded(self._sum_fwd, a)

        def forward(z):
            z = softagg.as_stored(z)
            c = softagg.channel_max(
                z, eps, valid,
                small_gather if self.partitioned else lambda row: row)
            out = lanes(softagg.table(z, c, t, eps))
            y, den = softagg.combine(z, out[:, :F], out[:, F:])
            return y, (z, c, den)

        @jax.custom_vjp
        def agg(z):
            return forward(z)[0]

        def bwd(res, g):
            z, c, den = res
            r = lanes(softagg.cotangent_over_den(g, den))
            return (softagg.input_cotangent(g, r, z, c, t, eps),)

        agg.defvjp(forward, bwd)
        return agg(x)

    def rel_aggregate(self, x: jax.Array, order: str,
                      rels=None) -> jax.Array:
        """The relation aggregation ``sum_r mean_r(.)`` of a typed
        graph as ONE weighted sum over the union edge list, on the
        side of the product ``order`` names: ``transform_first`` sums
        src-stacked rows into vertices, ``gather_first`` vertices
        into dst-stacked rows.  Its backward is the pass over the
        transposed table with the same per-edge weights — exact for
        any graph, scatter-free, and it keeps nothing; 'segment', the
        edge-list reference, is autodiff through the forward.
        ``rels``: the relations a cut layer sums (``Model.loss_cut``),
        forward and backward over the tables of that subset, whose
        stacks hold those relations' blocks alone; None sums all."""
        fwd_pass, bwd_pass = ORDER_PASSES[order]
        t = self.tables
        if t.edge_list:
            return t.apply(x, fwd_pass, rels)

        @jax.custom_vjp
        def agg(x):
            return t.apply(x, fwd_pass, rels)

        def fwd(x):
            return agg(x), None

        def bwd(_, g):
            return (t.apply(g, bwd_pass, rels),)

        agg.defvjp(fwd, bwd)
        return self._lane_padded(agg, x)

    def relation_plan(self, ops=(), typed=None, loss_ops=None) -> dict:
        """What the typed graph resolved to, for the run manifest's
        ``resolved`` beside :meth:`agg_window`: the kinds, one
        ``relations`` entry a relation, one ``rel_layers`` entry a
        relational layer (its ``rel_order``, the widths on the two
        sides, the width its scan runs at, stacked rows, table slots a
        forward / backward pass gathers and ``agg_slot_fill``, stored
        relation edges over the forward's slots), and the trainable
        input rows.  ``ops`` is the eval program's op list and every
        key above is that program's; ``loss_ops`` the loss program's
        (``Model.loss_cut``; ``ops`` again when nothing is cut), and
        what that one runs of the layer is beside them:
        ``train_relations`` of ``n_rel``, their ``train_edges``, the
        slots its passes gather (``train_slots_fwd`` / ``_bwd``) and
        the rows the layer hands on (``train_out_rows``).  Empty for
        an untyped model."""
        if typed is None:
            return {}
        slots = self.tables.slots()
        layers = []
        for i, op in enumerate(ops):
            if op.kind != "rel_aggregate":
                continue
            order = op.attrs["order"]
            fwd, bwd = ORDER_PASSES[order]
            f, b = slots.get((fwd, None)), slots.get((bwd, None))
            cut = (loss_ops or ops)[i].attrs
            rels = cut.get("rels")
            kinds = cut.get("kinds", range(len(typed.node_types)))
            layers.append({
                "op": i, "layer": op.attrs["layer"], "rel_order": order,
                "in_dim": op.attrs["in_dim"],
                "out_dim": op.attrs["out_dim"], "gather_width": op.dim,
                "scan_width": self._lane_width(op.dim),
                "stacked_rows": (typed.src_rows
                                 if order == TRANSFORM_FIRST
                                 else typed.dst_rows),
                "slots_fwd": f, "slots_bwd": b,
                "agg_slot_fill": (round(typed.num_edges / f, 4)
                                  if f and not self.tables.edge_list
                                  else None),
                "train_relations": (len(rels) if rels
                                    else op.attrs["n_rel"]),
                "train_edges": (typed.restrict(rels) if rels
                                else typed).num_edges,
                "train_slots_fwd": slots.get((fwd, rels)),
                "train_slots_bwd": slots.get((bwd, rels)),
                "train_out_rows": sum(typed.node_types[k]
                                      for k in kinds)})
        emb = [op for op in ops if op.kind == "typed_input"]
        rows = sum(op.attrs["embed_rows"] for op in emb)
        return {"node_types": list(typed.node_types),
                "relations": typed.describe(),
                "relation_edges": typed.num_edges,
                "rel_layers": layers, "embedding_rows": rows,
                "embedding_bytes": rows * emb[0].dim * 4 if emb else 0}

    def gat_attention(self, x: jax.Array, a_src: jax.Array,
                      a_dst: jax.Array,
                      neg_slope: float = 0.2) -> jax.Array:
        """Additive-attention aggregation (ops/attention.py): per
        destination row, softmax over its neighbors of
        ``LeakyReLU(a_src.h_j + a_dst.h_i)`` weighting the neighbor
        sum, per head.  Needs the ELL tables (every row's neighborhood
        in one bucket row) or the flat8 tables (a row's sub-rows
        combined by sorted scatters).

        Gradients, as :meth:`aggregate_sum` chooses them: on a
        symmetric graph the bucketed layout's backward is a second
        pass over the same tables, each row gathering from the rows it
        feeds (``gat_ell_backward``; the cotangent and the packed row
        statistics ride the same halo as the forward's features);
        ``symmetric=False`` and the flat layout are exact autodiff
        through the forward."""
        t = self.tables
        if isinstance(t, RingTables):
            raise NotImplementedError(
                "attention is not supported with halo='ring' (the ring "
                "accumulator is additive; the edge softmax needs the "
                "whole neighborhood); use halo='gather'")
        flat8 = isinstance(t, FlatTables)
        if not flat8 and not (isinstance(t, EllTables) and t.idx):
            raise NotImplementedError(
                f"attention needs the ELL tables (aggr_impl='ell') or "
                f"the flat8 layout (aggr_impl='attn_flat8'), got "
                f"{self.aggr_impl!r}; sectioned splits a row's "
                "neighbors across sections and cannot host the edge "
                "softmax")
        if a_src.ndim == 1:                  # single-head vectors
            a_src = a_src[None, :]
            a_dst = a_dst[None, :]
        if flat8 or not self.symmetric:
            return self._gat_fwd(x, a_src, a_dst, neg_slope, flat8)
        from ..ops.attention import gat_ell_backward, gat_ell_forward
        tables = (t.idx, t.row_id, t.row_pos, self.num_rows)

        @jax.custom_vjp
        def attn(x, a_src, a_dst):
            return self._gat_fwd(x, a_src, a_dst, neg_slope, flat8)

        def fwd(x, a_src, a_dst):
            out, c, stats = gat_ell_forward(
                *self._gat_scores(x, a_src, a_dst), *tables,
                neg_slope=neg_slope)
            return out, (x, a_src, a_dst, out, c, stats)

        def bwd(res, g):
            return gat_ell_backward(*res, g, self.gathered_with_zero,
                                    *tables, neg_slope=neg_slope)

        attn.defvjp(fwd, bwd)
        return attn(x, a_src, a_dst)

    def _gat_scores(self, x: jax.Array, a_src: jax.Array,
                    a_dst: jax.Array):
        """The halo'd features and the per-vertex halves of the edge
        score: ``(full [G+1, K*dh], s_full [G+1, K], d_local
        [num_rows+1, K])``, a dummy slot last in each."""
        K, dh = a_src.shape
        full = self.gathered_with_zero(x)
        with jax.named_scope(ATTN_SCORES_SCOPE):
            # the K scores a vertex are kept in fp32 whatever the
            # compute dtype: they feed exp(), where a bf16 rounding of
            # a score of magnitude 8 is a 3% error in the edge's weight
            fullr = full.reshape(full.shape[0], K, dh)
            s_full = jnp.einsum(
                "gkd,kd->gk", fullr, a_src.astype(full.dtype),
                preferred_element_type=jnp.float32)     # [G+1, K]
            d = jnp.einsum(
                "vkd,kd->vk", x.reshape(x.shape[0], K, dh),
                a_dst.astype(x.dtype),
                preferred_element_type=jnp.float32)     # [num_rows, K]
            d_local = jnp.concatenate(
                [d, jnp.zeros((1, K), dtype=d.dtype)])
        return full, s_full, d_local

    def _gat_fwd(self, x: jax.Array, a_src: jax.Array, a_dst: jax.Array,
                 neg_slope: float, flat8: bool) -> jax.Array:
        """Halo exchange + the layout's attention forward — the eval
        program, and what autodiff goes through where it is the
        gradient."""
        from ..ops.attention import (gat_aggregate_ell,
                                     gat_aggregate_flat8,
                                     resolve_dh_chunk)
        t = self.tables
        full, s_full, d_local = self._gat_scores(x, a_src, a_dst)
        if flat8:
            K, dh = a_src.shape
            return gat_aggregate_flat8(full, s_full, d_local,
                                       t.idx, t.dst, self.num_rows,
                                       neg_slope=neg_slope,
                                       dh_chunk=resolve_dh_chunk(
                                           self.num_rows, K, dh))
        return gat_aggregate_ell(full, s_full, d_local, t.idx,
                                 t.row_id, t.row_pos, self.num_rows,
                                 neg_slope=neg_slope)

    def transformer_attention(self, q: jax.Array, kv: jax.Array,
                              r: jax.Array, w_beta: jax.Array,
                              heads: int, concat: bool = True,
                              rate: float = 0.0,
                              seed: Optional[jax.Array] = None
                              ) -> jax.Array:
        """The Graph Transformer layer's aggregation and root path
        (``ops/attention.py``, ``models/gtrans.py`` has the equations):
        ``m`` the dot-product attention of the rows' queries ``q``
        ``[rows, K*d]`` over their neighbours' ``[k | v]`` rows ``kv``
        ``[rows, 2*K*d]``, heads concatenated or (``concat`` False)
        averaged; ``beta = sigmoid(w_beta . [m; r; m - r])`` a row; out
        ``beta r + (1 - beta) m``.  ``rate`` > 0 with a ``seed``
        (``uint32[2]`` from the step's key) drops edges of the softmax
        per head (:func:`ops.attention.edge_keep_scale`); eval passes
        neither.  The attention's gradient is the hand-written two-pass
        rule (``dot_ell_backward``); the gate's is autodiff.  Needs the
        ELL tables of a symmetric graph on one partition: anything
        else is refused by name."""
        t = self.tables
        if isinstance(t, FlatTables):
            raise NotImplementedError(TFATTN_FLAT8_REFUSAL)
        if not (isinstance(t, EllTables) and t.idx):
            raise NotImplementedError(
                f"the dot-product attention needs the ELL tables "
                f"(aggr_impl='ell'), got {self.aggr_impl!r}")
        if not self.symmetric:
            raise NotImplementedError(TFATTN_DIRECTED_REFUSAL)
        if self.partitioned:
            raise NotImplementedError(TFATTN_PARTITION_REFUSAL)
        from ..ops.attention import (dot_ell_backward, dot_ell_forward,
                                     edge_keep_scale)
        d = q.shape[1] // heads
        keep = None
        if seed is not None and rate > 0:
            def keep(dst, src):
                return edge_keep_scale(dst, src, heads, seed, rate)
        tables = (t.idx, t.row_id, t.row_pos, self.num_rows)

        def forward(q, kv, residuals):
            # both passes of the backward recompute the scores from the
            # values the forward read: pin them (softagg.as_stored)
            q, kv = softagg.as_stored(q), softagg.as_stored(kv)
            got = dot_ell_forward(self.gathered_with_zero(kv), q, d,
                                  *tables, keep=keep,
                                  residuals=residuals)
            return got, q, kv

        @jax.custom_vjp
        def attend(q, kv):
            return forward(q, kv, False)[0]

        def fwd(q, kv):
            (m, stats), q, kv = forward(q, kv, True)
            return m, (q, kv, m, stats)

        def bwd(res, g):
            return dot_ell_backward(*res, g, d, self.gathered_with_zero,
                                    *tables, keep=keep)

        attend.defvjp(fwd, bwd)
        m = attend(q, kv)
        f32 = jnp.float32
        with jax.named_scope(ATTN_GATE_SCOPE):
            if not concat:
                m = m.reshape(m.shape[0], heads, d).mean(axis=1)
            rf = r.astype(f32)
            w = w_beta.astype(f32).reshape(3, -1)
            beta = jax.nn.sigmoid((m * w[0] + rf * w[1]
                                   + (m - rf) * w[2]).sum(axis=1))
            beta = beta[:, None]
            return (beta * rf + (1.0 - beta) * m).astype(r.dtype)

    def _max_fwd(self, x: jax.Array) -> jax.Array:
        """Neighbor max; rows with no neighbors yield 0.  Dummy/padding
        sources are masked out (their zero rows must not win the max)."""
        if isinstance(self.tables, RingTables):
            return self.tables.max(None, self.num_rows, self.aggr_impl)
        full = self.gathered_with_zero(x)
        out = self.tables.max(full, self.num_rows, self.aggr_impl)
        return jnp.where(jnp.isfinite(out), out, 0.0).astype(full.dtype)


# the context's traced arrays: one GraphArrays (models/tables.py), which
# keeps the parameter order programs have always had; every other
# field is static aux data
_GRAPH_FIELDS = ("tables", "in_degree", "real_rows")
_STATIC_FIELDS = tuple(f.name for f in fields(GraphContext)
                       if f.name not in _GRAPH_FIELDS)


def _gctx_flatten(g: GraphContext):
    return ((GraphArrays(g.tables, g.in_degree, g.real_rows),),
            tuple(getattr(g, n) for n in _STATIC_FIELDS))


def _gctx_unflatten(aux, children):
    (graph,) = children
    return GraphContext(tables=graph.tables, in_degree=graph.in_degree,
                        real_rows=graph.real_rows,
                        **dict(zip(_STATIC_FIELDS, aux)))


# GraphContext is a pytree so the graph tables travel as jit ARGUMENTS.
# Closure-capturing them embeds the edge/ELL index arrays (hundreds of
# MB at Reddit scale) as HLO *constants* — bloating the executable and
# the compile, and keying the compile cache on the graph's contents.
# The callables/static config ride in aux_data; the same context
# object is passed every step, so jit's static-equality check hits
# the cache.
jax.tree_util.register_pytree_node(GraphContext, _gctx_flatten,
                                   _gctx_unflatten)


def _computed_again(run):
    """``run(params, *xs) -> outputs`` as the backward keeps it under
    remat: its inputs and nothing of its insides, which the backward
    computes again — under the ``roc.recompute`` scope — when the
    outputs' cotangent arrives, and not before.  Two barriers hold
    XLA to that: ``jax.checkpoint`` alone leaves the scheduler free to
    start a run's recomputation early and to finish its gradients
    late, and the TPU compiler did both — three runs' recomputed
    products were live at the peak, and remat saved a tenth of what it
    should (PERF.md section 6, PR 33)."""

    @jax.custom_vjp
    def seg(p, *xs):
        return run(p, *xs)

    def fwd(p, *xs):
        return run(p, *xs), (p, xs)

    def bwd(res, g):
        (p, xs), g = jax.lax.optimization_barrier((res, g))
        with jax.named_scope(RECOMPUTE_SCOPE):
            _, pull = jax.vjp(run, p, *xs)
        # the second barrier: every gradient of the run, the weights'
        # too, before the backward moves on — left alone, XLA puts the
        # weight-gradient products off to the end of the backward and
        # holds each run's cotangent (and what was computed again for
        # it) until then
        return jax.lax.optimization_barrier(pull(g))

    seg.defvjp(fwd, bwd)
    return seg


@dataclass(frozen=True)
class TensorHandle:
    """Symbolic tensor produced by builder calls (the analog of the
    reference's ``Tensor`` value, ``gnn.h:132-158``)."""
    idx: int
    dim: int


@dataclass
class _Op:
    kind: str
    inputs: Tuple[int, ...]
    dim: int
    param: Optional[str] = None        # param-dict key for linear ops
    attrs: Dict[str, Any] = field(default_factory=dict)


class GcnLayer(NamedTuple):
    """A GCN-form layer of an op list (:meth:`Model.gcn_layers`): the
    bias-free ``linear`` op, the normalized sum ``ops[lo..hi]`` beside
    it (one ``fused_aggregate``, or ``indegree_norm -> scatter_gather
    -> indegree_norm``), the side the sum runs on, the linear's two
    widths, and whether a parameter is among the ancestors of the
    layer's input."""
    linear: int
    lo: int
    hi: int
    order: str
    in_dim: int
    out_dim: int
    trained: bool

    @property
    def sum_op(self) -> int:
        """The op that sums over the edges."""
        return self.lo if self.lo == self.hi else self.lo + 1


class Model:
    """Builder + interpreter.  Mirrors the reference Model API
    (``gnn.h:162-203``); see module docstring."""

    def __init__(self, in_dim: int):
        self._ops: List[_Op] = [_Op("input", (), in_dim)]
        self._n_linear = 0
        self._n_gat = 0
        self._n_eps = 0
        self._n_bn = 0
        self._loss_op: Optional[int] = None
        # a typed model's kinds (models/rgcn.py): ``{"node_types",
        # "embed_types", "relations"}`` — None for every other family
        self.typed: Optional[Dict[str, Any]] = None

    def uses_relations(self) -> bool:
        """True for a typed model (``rel_aggregate`` ops): it runs on
        the relation tables of ``core/relations.py``, on one chip."""
        return any(op.kind == "rel_aggregate" for op in self._ops)

    def rel_orders(self) -> Tuple[str, ...]:
        """The resolved side of the mean of each relational layer, in
        layer order."""
        return tuple(op.attrs["order"] for op in self._ops
                     if op.kind == "rel_aggregate")

    def with_rel_orders(self, resolve) -> "Model":
        """The model with each relational layer's product on the side
        of its mean that ``resolve(in_dim, out_dim)`` names
        (``core/relations.py resolve_rel_order``): a layer is the
        adjacent pair ``rel_linear -> rel_aggregate``
        (``transform_first``: stacked products, then the sum into
        vertices) or ``rel_aggregate -> rel_linear``
        (``gather_first``: stacked means, then their products), and
        the rewrite swaps the pair in place — op indices, consumers
        and parameter names are untouched.  Returns ``self`` when
        nothing changes (``resolve_config`` is idempotent)."""
        ops = self._copied_ops()
        changed = False
        for i, op in enumerate(ops[:-1]):
            nxt = ops[i + 1]
            if {op.kind, nxt.kind} != {"rel_linear", "rel_aggregate"} \
                    or nxt.inputs != (i,):
                continue
            a = op.attrs
            order = resolve(a["in_dim"], a["out_dim"])
            if order == a["order"]:
                continue
            changed = True
            lin = op if op.kind == "rel_linear" else nxt
            base = {"layer": a["layer"], "in_dim": a["in_dim"],
                    "out_dim": a["out_dim"], "order": order,
                    "n_rel": a["n_rel"]}
            scale = self._stack_scale(order)
            if order == TRANSFORM_FIRST:
                ops[i] = _Op("rel_linear", op.inputs, a["out_dim"],
                             lin.param, {**base, "row_scale": scale})
                ops[i + 1] = _Op("rel_aggregate", (i,), a["out_dim"],
                                 attrs=dict(base))
            else:
                ops[i] = _Op("rel_aggregate", op.inputs, a["in_dim"],
                             attrs={**base, "row_scale": scale})
                ops[i + 1] = _Op("rel_linear", (i,), a["out_dim"],
                                 lin.param, dict(base))
        return self._with_ops(ops) if changed else self

    def _norm_sum(self, i: int, src: int, readers) -> Optional[int]:
        """The last op of the normalized sum ``D^-1/2 A D^-1/2`` that
        starts at op ``i`` and reads op ``src`` alone: a SUM
        ``fused_aggregate`` (``i``), or the ``indegree_norm ->
        scatter_gather(SUM) -> indegree_norm`` chain
        :meth:`fuse_norm_aggregate` folds (``i + 2``: its inner tensors
        read once and neither the loss's).  None for anything else."""
        ops = self._ops
        if i >= len(ops) or ops[i].inputs != (src,):
            return None
        if ops[i].kind == "fused_aggregate":
            return (i if ops[i].attrs.get("aggr", AGGR_SUM) == AGGR_SUM
                    else None)
        if (ops[i].kind == "indegree_norm" and i + 2 < len(ops)
                and ops[i + 1].kind == "scatter_gather"
                and ops[i + 1].inputs == (i,)
                and ops[i + 1].attrs.get("aggr", AGGR_SUM) == AGGR_SUM
                and ops[i + 2].kind == "indegree_norm"
                and ops[i + 2].inputs == (i + 1,)
                and readers[i] == readers[i + 1] == 1
                and self._loss_op not in (i, i + 1)):
            return i + 2
        return None

    def gcn_layers(self) -> List[GcnLayer]:
        """Every GCN-form layer of the op list, in op order: a
        bias-free ``linear`` and a normalized sum (:meth:`_norm_sum`)
        right after it — ``linear_first``: the linear has no activation
        and the sum is its one reader, not the loss's — or right before
        it, where :meth:`with_agg_order` moved the sum onto the
        linear's input (``aggregate_first``, marked on the linear)."""
        ops = self._ops
        readers = [0] * len(ops)
        trained = [False] * len(ops)
        for i, op in enumerate(ops):
            for j in op.inputs:
                readers[j] += 1
            trained[i] = op.param is not None or any(
                trained[j] for j in op.inputs)
        out = []
        for i, op in enumerate(ops):
            if op.kind != "linear" or op.attrs.get("bias"):
                continue
            if op.attrs.get("agg_order") == AGGREGATE_FIRST:
                hi = op.inputs[0]
                lo = hi if ops[hi].kind == "fused_aggregate" else hi - 2
                out.append(GcnLayer(i, lo, hi, AGGREGATE_FIRST,
                                    op.attrs["in_dim"], op.dim,
                                    trained[ops[lo].inputs[0]]))
                continue
            if (op.attrs.get("activation", AC_MODE_NONE) != AC_MODE_NONE
                    or readers[i] != 1
                    or self._loss_op == i):
                continue
            hi = self._norm_sum(i + 1, i, readers)
            if hi is not None:
                out.append(GcnLayer(i, i + 1, hi, LINEAR_FIRST,
                                    op.attrs["in_dim"], op.dim,
                                    trained[op.inputs[0]]))
        return out

    def with_agg_order(self, resolve) -> "Model":
        """The model with each ``linear_first`` GCN-form layer
        (:meth:`gcn_layers`) whose sum ``resolve(in_dim, out_dim,
        trained)`` puts first (``core/relations.py resolve_agg_order``)
        rewritten in place to the same normalized sum over the linear's
        input, then the linear: ``Â (X W) = (Â X) W``, the linear being
        bias-free and the norms diagonal.  A fused sum's activation
        moves onto the linear; an unfused chain's ``relu`` stays the op
        it was, now reading the linear.  The linear keeps its name and
        every later op its index and its inputs; the marker
        ``agg_order`` on the linear says the layer was moved.  Returns
        ``self`` when nothing changes (``resolve_config`` is
        idempotent)."""
        ops = self._copied_ops()
        changed = False
        for layer in self.gcn_layers():
            if layer.order != LINEAR_FIRST or resolve(
                    layer.in_dim, layer.out_dim,
                    layer.trained) != AGGREGATE_FIRST:
                continue
            changed = True
            lin, lo, hi = layer.linear, layer.lo, layer.hi
            src, attrs = ops[lin].inputs, {**ops[lin].attrs,
                                           "agg_order": AGGREGATE_FIRST}
            if lo == hi:
                act = ops[lo].attrs.get("activation", AC_MODE_NONE)
                ops[lin] = _Op("fused_aggregate", src, layer.in_dim,
                               attrs={**ops[lo].attrs,
                                      "activation": AC_MODE_NONE})
                attrs["activation"] = act
            else:
                ops[lin] = _Op("indegree_norm", src, layer.in_dim)
                ops[lo] = _Op("scatter_gather", (lin,), layer.in_dim,
                              attrs=dict(ops[lo + 1].attrs))
                ops[lo + 1] = _Op("indegree_norm", (lo,), layer.in_dim)
            ops[hi] = _Op("linear", (hi - 1,), layer.out_dim,
                          self._ops[lin].param, attrs)
        return self._with_ops(ops) if changed else self

    def _copied_ops(self) -> List[_Op]:
        return [_Op(o.kind, o.inputs, o.dim, o.param, dict(o.attrs))
                for o in self._ops]

    def _with_ops(self, ops: List[_Op]) -> "Model":
        """This model over the rewritten op list ``ops``."""
        new = Model(in_dim=ops[0].dim)
        new._ops = ops
        new._n_linear, new._n_gat, new._n_eps = (
            self._n_linear, self._n_gat, self._n_eps)
        new._n_bn = self._n_bn
        new._loss_op = self._loss_op
        new.typed = self.typed
        return new

    def loss_cut(self) -> "Model":
        """The model the *loss* program runs (:meth:`loss_fn` in train
        mode; the eval / predict program runs ``self``, every row of
        every kind): where the loss reads a proper subset of the rows
        (:meth:`labelled`: a typed model's kind 0), the last relational
        layer computes those rows alone.  Its ``rel_aggregate`` sums
        only the relations that end in the labelled kind (``rels``,
        indices into ``typed["relations"]``, over the tables of
        ``core/relations.py TypedGraph.restrict``), its ``rel_linear``
        multiplies those relations' blocks, its ``root_linear`` the
        labelled kind's rows (``kinds``), and the ``add`` joins arrays
        a kind tall — what is left out is values no output of the loss
        program depends on, so loss and gradients are the uncut op
        list's.  THE description of the cut: the interpreter, the
        relation tables (``train/trainer.py``), the ``plan`` line and
        the memory plan (``row_scale``: each array at its new height)
        read these attrs.  Op indices, parameter names and layer 1 are
        untouched; ``self`` when every row is labelled, when no
        relation ends in the labelled kind, or when the op list does
        not end in ``add(rel pair, root_linear)`` of one tensor."""
        ty, ops, out = self.typed, self._ops, self._loss_op
        if not ty or len(ty["node_types"]) < 2 or out is None \
                or ops[out].kind != "add":
            return self
        readers = {j: [i for i, op in enumerate(ops) if j in op.inputs]
                   for j in range(len(ops))}
        pair = {"rel_linear", "rel_aggregate"}
        hi = next((j for j in ops[out].inputs if ops[j].kind in pair), 0)
        ro = next((j for j in ops[out].inputs
                   if ops[j].kind == "root_linear"), 0)
        lo = hi - 1
        keep = tuple(r for r, (_, d) in enumerate(ty["relations"])
                     if d == 0)
        if not (hi and ro and keep
                and {ops[lo].kind, ops[hi].kind} == pair
                and ops[hi].inputs == (lo,)
                and ops[lo].inputs == ops[ro].inputs
                and "rels" not in ops[hi].attrs
                and [readers[j] for j in (lo, hi, ro, out)]
                == [[hi], [out], [out], []]):
            return self
        V = sum(ty["node_types"])
        rows = ty["node_types"][0] / V
        ops = self._copied_ops()
        # the loss reads every row the cut list's last op makes
        ops[0].attrs.pop("label_scale", None)
        order = ops[hi].attrs["order"]
        ops[lo].attrs.update(rels=keep, kinds=(0,),
                             row_scale=self._stack_scale(order, keep))
        ops[hi].attrs.update(rels=keep, kinds=(0,), row_scale=rows)
        ops[ro].attrs.update(kinds=(0,), row_scale=rows)
        ops[out].attrs.update(row_scale=rows)
        return self._with_ops(ops)

    def rel_cuts(self) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
        """``(order, rels)`` of each cut relational layer
        (:meth:`loss_cut`): the relation tables it needs beside the
        whole ones."""
        return tuple((op.attrs["order"], op.attrs["rels"])
                     for op in self._ops
                     if op.kind == "rel_aggregate" and "rels" in op.attrs)

    def labelled(self, *arrays):
        """``arrays`` (logits, labels, mask: a row a vertex) cut to
        the rows that can carry a label: untouched, or a typed model's
        kind 0 — the loss and the metrics read those rows alone (the
        mask is ``None`` everywhere else), so their fp32 softmax is a
        kind tall, not ``V``."""
        if not self.typed:
            return arrays
        return tuple(a[:self.typed["node_types"][0]] for a in arrays)

    def uses_attention(self) -> bool:
        """True when the op list contains an attention op (``gat``,
        ``transformer_attention``) — such models run on the ELL tables
        or, additive attention alone, the flat8 tables
        (train/trainer.py resolve_attention_impl picks by edge
        count)."""
        return any(op.kind in ("gat", "transformer_attention")
                   for op in self._ops)

    def uses_dot_attention(self) -> bool:
        """True when the op list contains a ``transformer_attention``:
        the bucketed ELL tables of a symmetric graph on one partition,
        nothing else (``GraphContext.transformer_attention``)."""
        return any(op.kind == "transformer_attention"
                   for op in self._ops)

    def uses_max_aggregation(self) -> bool:
        """True when any scatter_gather op is MAX/MIN — those have no
        sectioned/bdense implementation and no ring form, so the
        trainers' impl resolver forces 'ell' and rejects halo='ring'
        up front (same policy as attention)."""
        return any(op.kind == "scatter_gather"
                   and op.attrs.get("aggr") in (AGGR_MAX, AGGR_MIN)
                   for op in self._ops)

    def num_fused_aggregates(self) -> int:
        """Fused norm-aggregate-norm ops in the list (0 for models
        :meth:`fuse_norm_aggregate` has not been applied to, or whose
        shape has no fusable chain)."""
        return sum(op.kind == "fused_aggregate" for op in self._ops)

    def fuse_norm_aggregate(self) -> "Model":
        """Rewrite every ``indegree_norm -> scatter_gather(SUM) ->
        indegree_norm [-> relu]`` chain whose intermediates have no
        other consumer (and don't carry the loss marker) into ONE
        ``fused_aggregate`` op computing ``[relu](D^-1/2 A D^-1/2 x)``
        — the GCN normalization sandwich (``gnn.cc:78-91``) folded
        into the aggregation so the 2-3 extra full ``[V, F]`` HBM
        round trips per layer disappear (GraphContext.aggregate_fused
        picks table-baked weights or in-op scaling per impl).

        Returns a NEW Model; parameter names are untouched (the chain
        is parameter-free), so params initialized from either model
        feed both — checkpoints stay compatible.  Models with no
        matching chain come back as an equivalent copy with
        ``num_fused_aggregates() == 0``."""
        ops = self._ops
        n = len(ops)
        consumers = [0] * n
        for op in ops:
            for i in op.inputs:
                consumers[i] += 1
        loss = self._loss_op
        # chain start -> (chain end inclusive, fused activation)
        chains: Dict[int, Tuple[int, str]] = {}
        i = 1
        while i + 2 < n:
            if not (ops[i].kind == "indegree_norm" and self._norm_sum(
                    i, ops[i].inputs[0], consumers) is not None):
                i += 1
                continue
            end, act = i + 2, AC_MODE_NONE
            if (end + 1 < n and ops[end + 1].kind == "activation"
                    and ops[end + 1].attrs.get("mode") == AC_MODE_RELU
                    and ops[end + 1].inputs == (end,)
                    and consumers[end] == 1 and loss != end):
                end += 1
                act = AC_MODE_RELU
            chains[i] = (end, act)
            i = end + 1
        fused = Model(in_dim=ops[0].dim)
        fused._n_linear = self._n_linear
        fused._n_gat = self._n_gat
        fused._n_eps = self._n_eps
        fused._n_bn = self._n_bn
        new_ops = [ops[0]]
        remap = {0: 0}
        skip_until = 0
        for i in range(1, n):
            if i in chains:
                end, act = chains[i]
                new_ops.append(_Op(
                    "fused_aggregate", (remap[ops[i].inputs[0]],),
                    ops[i].dim,
                    attrs={"aggr": AGGR_SUM, "activation": act}))
                for k in range(i, end + 1):
                    remap[k] = len(new_ops) - 1
                skip_until = end
                continue
            if i <= skip_until:
                continue
            op = ops[i]
            new_ops.append(_Op(
                op.kind, tuple(remap[k] for k in op.inputs), op.dim,
                op.param, dict(op.attrs)))
            remap[i] = len(new_ops) - 1
        fused._ops = new_ops
        fused._loss_op = remap[loss] if loss is not None else None
        return fused

    # ---- builder API (names match the reference) ----

    def input(self) -> TensorHandle:
        return TensorHandle(0, self._ops[0].dim)

    def dropout(self, t: TensorHandle, rate: float = 0.5) -> TensorHandle:
        return self._append("dropout", (t.idx,), t.dim, attrs={"rate": rate})

    def linear(self, t: TensorHandle, out_dim: int,
               activation: str = AC_MODE_NONE,
               bias: bool = False) -> TensorHandle:
        """``t @ linear_<n>`` (bias-free, the reference's); ``bias``
        adds the row vector ``linear_<n>_b`` — ``torch.nn.Linear``'s,
        asked for by the families whose published parameter count
        holds it (models/deepergcn.py)."""
        name = f"linear_{self._n_linear}"
        self._n_linear += 1
        attrs = {"activation": activation, "in_dim": t.dim}
        if bias:
            attrs["bias"] = True
        return self._append("linear", (t.idx,), out_dim, param=name,
                            attrs=attrs)

    def batch_norm(self, t: TensorHandle) -> TensorHandle:
        """``torch.nn.BatchNorm1d`` over the vertex axis
        (``ops/norm.py``): in train mode the moments over ALL real
        vertex rows, in eval mode the running statistics.  Parameters
        ``bn_<n>_scale`` / ``bn_<n>_shift`` and — state, not
        parameters: :meth:`state_names` — ``bn_<n>_mean`` /
        ``bn_<n>_var``, all float32 whatever the compute dtype."""
        name = f"{BN_PARAM_PREFIX}{self._n_bn}"
        self._n_bn += 1
        return self._append("batch_norm", (t.idx,), t.dim, param=name,
                            attrs={"eps": BN_EPS,
                                   "momentum": BN_MOMENTUM})

    def soft_aggregate(self, t: TensorHandle, temperature: float,
                       eps: float = 1e-7) -> TensorHandle:
        """``t + sum_u sg(softmax_u(temperature * m_u)) * m_u`` with
        ``m = relu(t) + eps``: GENConv's ``softmax_sg`` neighbour
        aggregation with its self term
        (``GraphContext.soft_aggregate``)."""
        return self._append("soft_aggregate", (t.idx,), t.dim,
                            attrs={"t": float(temperature),
                                   "eps": float(eps)})

    def indegree_norm(self, t: TensorHandle) -> TensorHandle:
        return self._append("indegree_norm", (t.idx,), t.dim)

    def scatter_gather(self, t: TensorHandle,
                       aggr: str = AGGR_SUM) -> TensorHandle:
        return self._append("scatter_gather", (t.idx,), t.dim,
                            attrs={"aggr": aggr})

    def gat_attention(self, t: TensorHandle, neg_slope: float = 0.2,
                      heads: int = 1) -> TensorHandle:
        """Attention-weighted neighbor aggregation (the GAT layer's
        core, ops/attention.py).  ``heads`` K-way splits the feature
        axis: each head attends independently over its dim/K slice and
        the outputs concatenate (the GAT paper's multi-head concat
        form).  Adds two learned [K, dim/K] attention weights
        (``gat_N_src`` / ``gat_N_dst``) to the params."""
        if t.dim % heads:
            raise ValueError(
                f"gat_attention: dim {t.dim} not divisible by "
                f"heads {heads}")
        name = f"gat_{self._n_gat}"
        self._n_gat += 1
        return self._append("gat", (t.idx,), t.dim, param=name,
                            attrs={"neg_slope": neg_slope,
                                   "heads": heads})

    def transformer_attention(self, q: TensorHandle, kv: TensorHandle,
                              r: TensorHandle, heads: int,
                              concat: bool = True,
                              rate: float = 0.0) -> TensorHandle:
        """The Graph Transformer layer's attention and gated root path
        over three projections of one input: the queries ``q`` (``K *
        d`` wide), the keys and values side by side ``kv`` (``2 * K *
        d``: the table the edges gather) and the root ``r`` (``K * d``
        with ``concat``, else ``d``: the heads are averaged).
        ``rate``: the attention dropout, per edge and head, in training
        (``GraphContext.transformer_attention``).  Adds the gate's
        ``tfattn_<n>_beta`` ``[3 * out]`` to the params."""
        F = q.dim
        if F % heads or kv.dim != 2 * F:
            raise ValueError(
                f"transformer_attention: queries {F} wide must split "
                f"into {heads} heads and [k | v] be {2 * F} wide, got "
                f"{kv.dim}")
        out = F if concat else F // heads
        if r.dim != out:
            raise ValueError(f"transformer_attention: the root path is "
                             f"{r.dim} wide, the output {out}")
        if not 0.0 <= rate < 1.0:
            raise ValueError(f"attention dropout {rate} not in [0, 1)")
        n = sum(op.kind == "transformer_attention" for op in self._ops)
        return self._append(
            "transformer_attention", (q.idx, kv.idx, r.idx), out,
            param=f"tfattn_{n}",
            attrs={"heads": heads, "head_width": F // heads,
                   "concat": concat, "rate": float(rate)})

    def layer_norm(self, t: TensorHandle) -> TensorHandle:
        """``torch.nn.LayerNorm`` over each row's channels
        (``ops/norm.py layer_norm``): parameters ``ln_<n>_scale`` /
        ``ln_<n>_shift``, float32 whatever the compute dtype."""
        n = sum(op.kind == "layer_norm" for op in self._ops)
        return self._append("layer_norm", (t.idx,), t.dim,
                            param=f"{LN_PARAM_PREFIX}{n}",
                            attrs={"eps": LN_EPS})

    def relu(self, t: TensorHandle) -> TensorHandle:
        return self._append("activation", (t.idx,), t.dim,
                            attrs={"mode": AC_MODE_RELU})

    def sigmoid(self, t: TensorHandle) -> TensorHandle:
        return self._append("activation", (t.idx,), t.dim,
                            attrs={"mode": AC_MODE_SIGMOID})

    def elu(self, t: TensorHandle) -> TensorHandle:
        """Beyond the reference's ActiMode set (gnn.h:82-86); used by
        the GAT family (models/gat.py)."""
        from ..ops.dense import AC_MODE_ELU
        return self._append("activation", (t.idx,), t.dim,
                            attrs={"mode": AC_MODE_ELU})

    def add(self, a: TensorHandle, b: TensorHandle) -> TensorHandle:
        assert a.dim == b.dim
        return self._append("add", (a.idx, b.idx), a.dim)

    def scale_add(self, a: TensorHandle, b: TensorHandle) -> TensorHandle:
        """``a + eps * b`` with a LEARNABLE scalar ``eps`` (zero-init).
        GIN's (1+eps) self-weight reduces to this on self-edged graphs:
        (1+eps)x + sum_{u != v} x_u == agg + eps*x (models/gin.py)."""
        assert a.dim == b.dim
        name = f"eps_{self._n_eps}"
        self._n_eps += 1
        return self._append("scale_add", (a.idx, b.idx), a.dim,
                            param=name)

    def mul(self, a: TensorHandle, b: TensorHandle) -> TensorHandle:
        assert a.dim == b.dim
        return self._append("mul", (a.idx, b.idx), a.dim)

    def lerp(self, a: TensorHandle, b: TensorHandle,
             alpha: float) -> TensorHandle:
        """``(1 - alpha) * a + alpha * b`` with a FIXED scalar — the
        APPNP teleport combine (models/appnp.py).  Distinct from
        :meth:`scale_add`, whose scalar is a learnable parameter."""
        assert a.dim == b.dim
        return self._append("lerp", (a.idx, b.idx), a.dim,
                            attrs={"alpha": float(alpha)})

    # ---- typed graphs (models/rgcn.py) ----

    def typed_input(self, t: TensorHandle, node_types, embed_types,
                    relations) -> TensorHandle:
        """``h^0`` of a typed graph, ``[V, F]`` in kind order: the
        file's feature rows for the kinds that have them (the model's
        input holds those rows alone, in kind order) and a trainable
        table ``embed_<k>`` ``[n_k, F]`` for each kind of
        ``embed_types``.  Declares the model typed: ``relations`` are
        the ordered kind pairs of ``core/relations.py derive_typed``."""
        self.typed = {"node_types": tuple(int(n) for n in node_types),
                      "embed_types": tuple(int(k) for k in embed_types),
                      "relations": tuple((int(s), int(d))
                                         for s, d in relations)}
        ty = self.typed
        rows = sum(ty["node_types"][k] for k in ty["embed_types"])
        V = sum(ty["node_types"])
        self._ops[0].attrs["row_scale"] = (V - rows) / V
        # kind 0 alone carries labels (Model.labelled)
        self._ops[0].attrs["label_scale"] = ty["node_types"][0] / V
        return self._append("typed_input", (t.idx,), t.dim,
                            param="embed",
                            attrs={"embed_rows": rows})

    def rel_conv(self, t: TensorHandle, out_dim: int,
                 layer: int) -> TensorHandle:
        """``sum_r mean_r(t W_r)`` over the relations into each
        vertex: a bias-free ``rel<layer>_<s>_<d>`` ``[in, out]`` a
        relation and ONE relation aggregation
        (``GraphContext.rel_aggregate``), recorded product first;
        :meth:`with_rel_orders` puts the product on the cheaper
        side."""
        ty = self.typed
        base = {"layer": layer, "in_dim": t.dim, "out_dim": out_dim,
                "order": TRANSFORM_FIRST, "n_rel": len(ty["relations"])}
        p = self._append("rel_linear", (t.idx,), out_dim,
                         param=f"rel{layer}",
                         attrs={**base, "row_scale": self._stack_scale(
                             TRANSFORM_FIRST)})
        return self._append("rel_aggregate", (p.idx,), out_dim,
                            attrs=dict(base))

    def root_linear(self, t: TensorHandle, out_dim: int,
                    layer: int) -> TensorHandle:
        """A vertex's own term: ``root<layer>_<k>`` ``[in, out]`` and
        the bias ``root<layer>_<k>_b`` ``[out]`` of its kind ``k``,
        over the kind's row range."""
        return self._append("root_linear", (t.idx,), out_dim,
                            param=f"root{layer}",
                            attrs={"layer": layer, "in_dim": t.dim,
                                   "n_kinds": len(
                                       self.typed["node_types"])})

    def softmax_cross_entropy(self, t: TensorHandle) -> TensorHandle:
        """Marks ``t`` as the logits fed to the masked CE loss (labels and
        mask arrive as apply() arguments, unlike the reference which binds
        label/mask tensors here, ``gnn.cc:92``)."""
        self._loss_op = t.idx
        return t

    def _append(self, kind: str, inputs: Tuple[int, ...], dim: int,
                param: Optional[str] = None,
                attrs: Optional[Dict[str, Any]] = None) -> TensorHandle:
        self._ops.append(_Op(kind, inputs, dim, param, attrs or {}))
        return TensorHandle(len(self._ops) - 1, dim)

    # ---- streaming support ----

    def streamable_head(self):
        """``(dropout_rate, linear_param_name, tail_model)`` when the op
        list starts ``input -> dropout -> linear`` and the first two
        intermediates have no other consumer — the pattern the
        host-feature streaming tier (core/streaming.py StreamedHead)
        can split off.  ``tail_model`` interprets ops[3:] against the
        projected ``[V, H]`` activations as its input and SHARES the
        original param names (do not call ``init_params`` on it).
        Returns None for any other head shape (e.g. GIN aggregates raw
        features; deep-GCN residuals consume the dropout output twice);
        callers fall back to in-HBM features or ring halo."""
        ops = self._ops
        if len(ops) < 4:
            return None
        if not (ops[1].kind == "dropout" and ops[1].inputs == (0,)):
            return None
        if not (ops[2].kind == "linear" and ops[2].inputs == (1,)):
            return None
        if ops[2].attrs.get("activation", AC_MODE_NONE) != AC_MODE_NONE:
            # StreamedHead computes a plain projection; a fused
            # activation would be silently dropped (and its gradient
            # mask missing from the streamed wgrad)
            return None
        for op in ops[3:]:
            if any(i < 2 for i in op.inputs):
                return None
        if self._loss_op is not None and self._loss_op < 3:
            return None
        tail = self._split_tail(2)
        return ops[1].attrs["rate"], ops[2].param, tail

    def _split_tail(self, head_out: int) -> "Model":
        """Tail model over ops past ``head_out`` (the streamed head's
        output tensor): the head output becomes the tail's input 0,
        later indices shift down, the loss marker shifts with them.
        Shared by streamable_head and streamable_agg_head — the remap
        must never drift between them."""
        ops = self._ops
        tail = Model(in_dim=ops[head_out].dim)
        for op in ops[head_out + 1:]:
            tail._ops.append(_Op(
                op.kind,
                tuple(0 if i == head_out else i - head_out
                      for i in op.inputs),
                op.dim, op.param, dict(op.attrs)))
        tail._loss_op = (self._loss_op - head_out
                         if self._loss_op is not None else None)
        return tail

    def streamable_agg_head(self):
        """``(prefix_ops, dropout_rate, linear_param, tail_model)``
        when the op list starts with a PARAMETER-FREE norm/aggregation
        chain from the input — ``(indegree_norm | scatter_gather
        SUM/AVG)+`` — followed by the ``dropout -> linear`` head
        pattern, with nothing later consuming the pre-head tensors.

        This is the SGC-family shape (aggregation applied to raw
        features, models/sgc.py): the prefix has no parameters, so the
        host tier evaluates it ONCE fully out-of-core
        (core/streaming.py stream_prefix_to_host — the reference's
        everything-host-resident ZC design, ``types.cu:22-32``) and
        every epoch then streams only the dropout/linear head.
        Returns None when there is no aggregation prefix (plain
        ``streamable_head`` covers that) or the shape doesn't match."""
        ops = self._ops
        i = 1
        while i < len(ops) and ops[i].inputs == (i - 1,) and (
                ops[i].kind in ("indegree_norm", "fused_aggregate")
                or (ops[i].kind == "scatter_gather"
                    and ops[i].attrs.get("aggr", AGGR_SUM)
                    in (AGGR_SUM, AGGR_AVG))):
            i += 1
        if i == 1 or not any(
                op.kind in ("scatter_gather", "fused_aggregate")
                for op in ops[1:i]):
            return None
        if i + 1 >= len(ops):
            return None
        if not (ops[i].kind == "dropout" and ops[i].inputs == (i - 1,)):
            return None
        if not (ops[i + 1].kind == "linear"
                and ops[i + 1].inputs == (i,)):
            return None
        if ops[i + 1].attrs.get("activation",
                                AC_MODE_NONE) != AC_MODE_NONE:
            return None
        head_out = i + 1
        for op in ops[head_out + 1:]:
            if any(j < head_out for j in op.inputs):
                return None
        # loss ON the head output is fine (classic SGC: the head linear
        # IS the classifier) — the tail degenerates to loss-on-input
        if self._loss_op is not None and self._loss_op < head_out:
            return None
        return (list(ops[1:i]), ops[i].attrs["rate"],
                ops[i + 1].param, self._split_tail(head_out))

    # ---- serving support ----

    GRAPH_OP_KINDS = ("scatter_gather", "fused_aggregate", "gat",
                      "indegree_norm", "rel_aggregate",
                      "soft_aggregate", "transformer_attention")

    def precompute_split(self):
        """``(prefix_ops, head_model)`` when the op list is a
        PARAMETER-FREE propagation prefix followed by a purely dense
        (row-wise) remainder — the SGC-family shape whose serving path
        collapses to "cache ``S^k X`` once, answer with a dense MLP"
        (``roc_tpu/serve``).  ``prefix_ops`` is the op sublist the
        export step evaluates host-side ONCE (the same vocabulary
        ``stream_prefix_to_host`` runs: ``indegree_norm`` /
        ``scatter_gather`` SUM/AVG / ``fused_aggregate``);
        ``head_model`` interprets the remaining ops against gathered
        prefix rows and SHARES the original param names.  Unlike
        :meth:`streamable_agg_head` the head keeps its dropout (eval
        mode drops nothing) and may be arbitrarily deep — the only
        requirement is that no graph op (and no reach-back past the
        prefix) remains below the split.  Returns None when the model
        has no parameter-free propagation prefix or the remainder
        still touches the graph."""
        ops = self._ops
        i = 1
        while i < len(ops) and ops[i].inputs == (i - 1,) and (
                ops[i].kind in ("indegree_norm", "fused_aggregate")
                or (ops[i].kind == "scatter_gather"
                    and ops[i].attrs.get("aggr", AGGR_SUM)
                    in (AGGR_SUM, AGGR_AVG))):
            i += 1
        if i == 1 or not any(
                op.kind in ("scatter_gather", "fused_aggregate")
                for op in ops[1:i]):
            return None
        if i >= len(ops):
            return None
        for op in ops[i:]:
            if op.kind in self.GRAPH_OP_KINDS:
                return None
            if any(j < i - 1 for j in op.inputs):
                return None
        if self._loss_op is not None and self._loss_op < i - 1:
            return None
        return list(ops[1:i]), self._split_tail(i - 1)

    def to_spec(self) -> Dict[str, Any]:
        """JSON-serializable description of the built model — the
        serving manifest persists this so a cold server process
        rebuilds the EXACT op list without the builder call that made
        it (``roc_tpu/serve/export.py``)."""
        return {
            "in_dim": self._ops[0].dim,
            "ops": [{"kind": op.kind, "inputs": list(op.inputs),
                     "dim": op.dim, "param": op.param,
                     "attrs": dict(op.attrs)}
                    for op in self._ops[1:]],
            "loss_op": self._loss_op,
            "counters": [self._n_linear, self._n_gat, self._n_eps]
            + ([self._n_bn] if self._n_bn else []),
            **({"typed": {k: [list(x) if isinstance(x, tuple) else x
                              for x in v]
                          for k, v in self.typed.items()}}
               if self.typed else {}),
        }

    @classmethod
    def from_spec(cls, spec: Dict[str, Any]) -> "Model":
        """Inverse of :meth:`to_spec`."""
        model = cls(in_dim=int(spec["in_dim"]))
        for op in spec["ops"]:
            model._ops.append(_Op(op["kind"], tuple(op["inputs"]),
                                  int(op["dim"]), op.get("param"),
                                  dict(op.get("attrs") or {})))
        model._loss_op = spec.get("loss_op")
        c = spec.get("counters") or [0, 0, 0]
        model._n_linear, model._n_gat, model._n_eps = (
            int(c[0]), int(c[1]), int(c[2]))
        model._n_bn = int(c[3]) if len(c) > 3 else 0
        ty = spec.get("typed")
        if ty:
            model.typed = {
                "node_types": tuple(ty["node_types"]),
                "embed_types": tuple(ty["embed_types"]),
                "relations": tuple(tuple(r) for r in ty["relations"])}
        return model

    # ---- params ----

    def init_params(self, key: jax.Array,
                    dtype=jnp.float32) -> Dict[str, jax.Array]:
        """Glorot-uniform for every linear weight: U(-s, s) with
        ``s = sqrt(6/(in+out))`` (``initializer_kernel.cu:38-48``)."""
        params: Dict[str, jax.Array] = {}
        for op in self._ops:
            if op.kind == "linear":
                key, sub = jax.random.split(key)
                in_dim = op.attrs["in_dim"]
                s = float(np.sqrt(6.0 / (in_dim + op.dim)))
                params[op.param] = jax.random.uniform(
                    sub, (in_dim, op.dim), dtype=dtype, minval=-s, maxval=s)
                if op.attrs.get("bias"):
                    # torch.nn.Linear's: U(-1/sqrt(in), 1/sqrt(in))
                    key, sub = jax.random.split(key)
                    b = float(1.0 / np.sqrt(in_dim))
                    params[f"{op.param}_b"] = jax.random.uniform(
                        sub, (op.dim,), dtype=dtype, minval=-b, maxval=b)
            elif op.kind == "batch_norm":
                # torch's: scale 1, shift 0, running mean 0, variance 1
                # — float32 whatever the parameters' dtype
                for suffix, fill in zip(BN_TRAINED + BN_STATE,
                                        (1.0, 0.0, 0.0, 1.0)):
                    params[f"{op.param}_{suffix}"] = jnp.full(
                        (op.dim,), fill, dtype=jnp.float32)
            elif op.kind == "layer_norm":
                # torch's: scale 1, shift 0 — float32 whatever the
                # parameters' dtype
                for suffix, fill in zip(LN_TRAINED, (1.0, 0.0)):
                    params[f"{op.param}_{suffix}"] = jnp.full(
                        (op.dim,), fill, dtype=jnp.float32)
            elif op.kind == "transformer_attention":
                # the gate's bias-free Linear(3 * out, 1): torch's
                # default, U(-1/sqrt(in), 1/sqrt(in))
                key, sub = jax.random.split(key)
                b = float(1.0 / np.sqrt(3 * op.dim))
                params[f"{op.param}_beta"] = jax.random.uniform(
                    sub, (3 * op.dim,), dtype=dtype, minval=-b, maxval=b)
            elif op.kind == "scale_add":
                # learnable GIN eps: zero-init (the paper's GIN-0)
                params[op.param] = jnp.zeros((), dtype=dtype)
            elif op.kind in ("typed_input", "rel_linear", "root_linear"):
                # Glorot-uniform like every matrix here (an embedding
                # table over its logical [rows, F] shape, as torch's
                # xavier_uniform_ on the OGB script's tables); biases 0
                for name, shape in self._typed_param_shapes(op):
                    if len(shape) == 1:
                        params[name] = jnp.zeros(shape, dtype=dtype)
                        continue
                    key, sub = jax.random.split(key)
                    s = float(np.sqrt(6.0 / (shape[0] + shape[1])))
                    params[name] = jax.random.uniform(
                        sub, shape, dtype=dtype, minval=-s, maxval=s)
            elif op.kind == "gat":
                # per head, the attention vectors are the [2*dh] -> 1
                # projection of the GAT paper split at the concat
                # boundary — Glorot over that logical shape
                heads = op.attrs.get("heads", 1)
                dh = op.dim // heads
                s = float(np.sqrt(6.0 / (2 * dh + 1)))
                for suffix in ("src", "dst"):
                    key, sub = jax.random.split(key)
                    params[f"{op.param}_{suffix}"] = jax.random.uniform(
                        sub, (heads, dh), dtype=dtype, minval=-s,
                        maxval=s)
        return params

    def state_names(self) -> Tuple[str, ...]:
        """The entries of the parameter dict that are state, not
        parameters: every ``batch_norm``'s running mean and variance.
        They travel with the parameters (checkpoints, ``predict``, the
        benchmark's reference read one dict), a train step hands new
        ones back (:meth:`loss_and_state`), and nothing optimizes
        them: no gradient, no Adam moments, no weight decay, no
        compute-dtype copy (``train/trainer.py split_state``)."""
        return tuple(f"{op.param}_{s}" for op in self._ops
                     if op.kind == "batch_norm" for s in BN_STATE)

    def _op_param_names(self, op: _Op) -> List[str]:
        """Every entry of the parameter dict ``op`` reads."""
        if op.kind == "linear":
            return [op.param] + ([f"{op.param}_b"]
                                 if op.attrs.get("bias") else [])
        if op.kind == "scale_add":
            return [op.param]
        if op.kind == "batch_norm":
            return [f"{op.param}_{s}" for s in BN_TRAINED + BN_STATE]
        if op.kind == "layer_norm":
            return [f"{op.param}_{s}" for s in LN_TRAINED]
        if op.kind == "transformer_attention":
            return [f"{op.param}_beta"]
        if op.kind in ("typed_input", "rel_linear", "root_linear"):
            return [n for n, _ in self._typed_param_shapes(op)]
        return []

    def _stack_scale(self, order: str, rels=None) -> float:
        """Rows of the order's stacked tensor a vertex: the src stack
        under ``transform_first``, the dst stack under
        ``gather_first`` (core/relations.py), of the relations
        ``rels`` (None: all)."""
        ty = self.typed
        end = 0 if order == TRANSFORM_FIRST else 1
        kept = (ty["relations"] if rels is None
                else [ty["relations"][r] for r in rels])
        return (sum(ty["node_types"][r[end]] for r in kept)
                / sum(ty["node_types"]))

    def _typed_param_shapes(self, op: _Op):
        """``[(name, shape), ...]`` of a typed op's parameters, in
        construction order."""
        ty = self.typed
        if op.kind == "typed_input":
            return [(f"{op.param}_{k}", (ty["node_types"][k], op.dim))
                    for k in ty["embed_types"]]
        if op.kind == "rel_linear":
            a = op.attrs
            return [(f"{op.param}_{s}_{d}", (a["in_dim"], a["out_dim"]))
                    for s, d in ty["relations"]]
        out = []
        for k in range(len(ty["node_types"])):
            out += [(f"{op.param}_{k}", (op.attrs["in_dim"], op.dim)),
                    (f"{op.param}_{k}_b", (op.dim,))]
        return out

    def _kind_ranges(self):
        off = np.concatenate([[0], np.cumsum(self.typed["node_types"])])
        return [(int(lo), int(hi)) for lo, hi in zip(off[:-1], off[1:])]

    def _eval_typed(self, op: _Op, x: jax.Array, params,
                    gctx: GraphContext) -> jax.Array:
        ty = self.typed
        ranges = self._kind_ranges()
        if op.kind == "typed_input":
            # roc.embed inside the op's own dense scope: assembling
            # h^0, and (JAX's transpose wrapper) slicing its cotangent
            # back into the tables' gradients
            with jax.named_scope(EMBED_SCOPE):
                blocks, at = [], 0
                for k, n in enumerate(ty["node_types"]):
                    if k in ty["embed_types"]:
                        blocks.append(
                            params[f"{op.param}_{k}"].astype(x.dtype))
                    else:
                        blocks.append(x[at:at + n])
                        at += n
                return jnp.concatenate(blocks, axis=0)
        n_types = ty["node_types"]
        # the rows and relations the layer computes: all of them, or a
        # cut layer's (loss_cut)
        kinds = list(op.attrs.get("kinds", range(len(n_types))))
        if op.kind == "root_linear":
            return dense.segment_linear(
                x, [ranges[k] for k in kinds],
                [n_types[k] for k in kinds],
                [(j, j) for j in range(len(kinds))],
                [params[f"{op.param}_{k}"] for k in kinds],
                [params[f"{op.param}_{k}_b"] for k in kinds])
        if op.kind == "rel_aggregate":
            return gctx.rel_aggregate(x, op.attrs["order"],
                                      op.attrs.get("rels"))
        # rel_linear: a block of rows a relation, on either side of the
        # mean (dense.segment_linear: segments in, segments out)
        rels = [ty["relations"][r] for r in op.attrs.get(
            "rels", range(len(ty["relations"])))]
        ws = [params[f"{op.param}_{s}_{d}"] for s, d in rels]
        if op.attrs["order"] == TRANSFORM_FIRST:
            # vertices in (a kind's rows, once a relation out of it),
            # the src stack out
            return dense.segment_linear(
                x, [ranges[s] for s, _ in rels],
                [n_types[s] for s, _ in rels],
                [(r, r) for r in range(len(rels))], ws)
        # the dst stack in, vertices out: a kind's rows sum the
        # products of the relations into it
        at = np.concatenate([[0], np.cumsum([n_types[d] for _, d in rels])])
        return dense.segment_linear(
            x, [(int(at[r]), int(at[r + 1])) for r in range(len(rels))],
            [n_types[k] for k in kinds],
            [(r, kinds.index(d)) for r, (_, d) in enumerate(rels)], ws)

    # ---- interpreter ----

    def apply(self, params: Dict[str, jax.Array], feats: jax.Array,
              gctx: GraphContext, key: Optional[jax.Array] = None,
              train: bool = True, remat: bool = False) -> jax.Array:
        """Run the recorded op list; returns the logits tensor
        (:meth:`apply_stateful` without the statistics)."""
        return self.apply_stateful(params, feats, gctx, key=key,
                                   train=train, remat=remat)[0]

    def apply_stateful(self, params: Dict[str, jax.Array],
                       feats: jax.Array, gctx: GraphContext,
                       key: Optional[jax.Array] = None,
                       train: bool = True, remat: bool = False
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Run the recorded op list; returns ``(logits, state)``:
        ``state`` holds, in train mode, the new value of every entry
        of :meth:`state_names` (each ``batch_norm``'s running
        statistics after this step's moments) and is empty in eval
        mode, which reads the running statistics and moves nothing.

        ``remat`` (the trainers' ``config.remat``, train steps only):
        every run of ops between two aggregations
        (``core/memory.py remat_segments``) is a checkpoint of its own
        (:func:`_computed_again`), so the backward keeps only what such
        a run reads — the aggregations' outputs and the few earlier
        tensors a run reaches back for — and computes a run's insides
        again when its turn comes, one run at a time.  The aggregations
        stay outside: their symmetric backward keeps nothing, and a
        checkpoint that
        took one in would keep its input in place of its output and
        pay the gather and the sum twice for the same bytes.  One
        checkpoint around the whole objective, which this replaces,
        kept the aggregates AND computed every run again before the
        first transpose: more memory than no remat at all (PERF.md
        section 6, PR 33)."""
        if (train and key is None and
                any(op.kind in ("dropout", "transformer_attention")
                    and op.attrs["rate"] > 0 for op in self._ops)):
            raise ValueError(
                "a PRNG key is required in train mode for models with "
                "dropout; pass key= or use train=False")
        ops = self._ops
        vals: Dict[int, jax.Array] = {0: feats}
        state: Dict[str, jax.Array] = {}
        out_idx = (self._loss_op if self._loss_op is not None
                   else len(ops) - 1)
        if not (remat and train):
            for i in range(1, len(ops)):
                vals[i] = self._eval_op(i, vals, params, gctx, key, train,
                                        state)
            return vals[out_idx], state
        read_at = {}                     # tensor -> last op that reads it
        for i, op in enumerate(ops):
            for j in op.inputs:
                read_at[j] = i
        segments = dict(remat_segments(ops))
        i = 1
        while i < len(ops):
            hi = segments.get(i)
            if hi is None:               # an aggregation: kept outside
                vals[i] = self._eval_op(i, vals, params, gctx, key, train,
                                        state)
                i += 1
                continue
            ins = sorted({j for op in ops[i:hi] for j in op.inputs
                          if j < i})
            outs = [k for k in range(i, hi)
                    if read_at.get(k, -1) >= hi or k == out_idx]

            names = {n for k in range(i, hi)
                     for n in self._op_param_names(ops[k])}

            def run(p, *xs, lo=i, hi=hi, ins=ins, outs=outs):
                local = dict(zip(ins, xs))
                moved: Dict[str, jax.Array] = {}
                for k in range(lo, hi):
                    local[k] = self._eval_op(k, local, p, gctx, key, train,
                                             moved)
                # a run's new statistics leave it as outputs, like its
                # tensors (their cotangent is zero)
                return tuple(local[k] for k in outs), moved

            got, moved = _computed_again(run)(
                {k: params[k] for k in names}, *(vals[j] for j in ins))
            vals.update(zip(outs, got))
            state.update(moved)
            i = hi
        return vals[out_idx], state

    def _eval_op(self, i: int, vals, params, gctx: GraphContext,
                 key: Optional[jax.Array], train: bool,
                 state: Optional[Dict[str, jax.Array]] = None
                 ) -> jax.Array:
        """Model op ``i`` on the tensors ``vals`` holds (index ->
        array), under the op's program scope.  A ``batch_norm`` in
        train mode writes its new running statistics into ``state``
        (name -> array)."""
        op = self._ops[i]
        x = vals[op.inputs[0]] if op.inputs else None
        # one scope per model op (obs/scopes.py): roc.agg.op<i> for
        # the aggregating kinds, roc.dense.op<i>.<kind> for the rest.
        # Entered here, outside the aggregations' custom_vjp, it is
        # still on the name stack when JAX traces their backward
        # (tests/test_scopes.py holds that)
        with jax.named_scope(op_scope(i, op.kind)):
            if op.kind == "dropout":
                sub = None
                if train and key is not None:
                    # the stream of the op's ordinal among the dropouts
                    sub = jax.random.fold_in(key, sum(
                        o.kind == "dropout" for o in self._ops[:i]))
                return dense.dropout(x, op.attrs["rate"], sub, train)
            if op.kind == "linear":
                # the output head = the LAST linear (the classifier in
                # every model family; the loss marker may sit on a
                # later norm / propagation op, e.g. GCN's final
                # indegree_norm)
                head = not any(o.kind == "linear"
                               for o in self._ops[i + 1:])
                if gctx.head_chunk and head \
                        and x.shape[0] > gctx.head_chunk:
                    # the classification head, chunked on the vertex
                    # axis: the compiled matmul is [head_chunk, C]
                    # regardless of V_p, so the head subprogram stays
                    # small and shape-stable (bit-identical values —
                    # each output row's dot product is unchanged; dW
                    # differs only in fp summation order)
                    return dense.linear_chunked(
                        x, params[op.param], op.attrs["activation"],
                        gctx.head_chunk,
                        bias=params.get(f"{op.param}_b"))
                return dense.linear(x, params[op.param],
                                    op.attrs["activation"],
                                    bias=params.get(f"{op.param}_b"))
            if op.kind == "batch_norm":
                scale, shift, mean, var = (
                    params[f"{op.param}_{s}"]
                    for s in BN_TRAINED + BN_STATE)
                if not train:
                    return batch_norm_eval(x, scale, shift, mean, var,
                                           op.attrs["eps"])
                y, bmean, bvar = gctx.batch_norm(x, scale, shift)
                if state is not None:
                    new = running_update(
                        mean, var, jax.lax.stop_gradient(bmean),
                        jax.lax.stop_gradient(bvar),
                        gctx.counted_rows, op.attrs["momentum"])
                    state[f"{op.param}_mean"] = new[0]
                    state[f"{op.param}_var"] = new[1]
                return y
            if op.kind == "soft_aggregate":
                return gctx.soft_aggregate(x, op.attrs["t"],
                                           op.attrs["eps"])
            if op.kind == "layer_norm":
                return layer_norm(x, *(params[f"{op.param}_{s}"]
                                       for s in LN_TRAINED),
                                  op.attrs["eps"])
            if op.kind == "transformer_attention":
                seed = None
                if train and key is not None and op.attrs["rate"] > 0:
                    # a stream of the op's ordinal among these ops
                    sub = jax.random.fold_in(jax.random.fold_in(
                        key, EDGE_DROPOUT_STREAM), sum(
                        o.kind == op.kind for o in self._ops[:i]))
                    seed = jax.random.bits(sub, (2,), jnp.uint32)
                q, kv, r = (vals[j] for j in op.inputs)
                return gctx.transformer_attention(
                    q, kv, r, params[f"{op.param}_beta"],
                    op.attrs["heads"], op.attrs["concat"],
                    op.attrs["rate"], seed)
            if op.kind == "indegree_norm":
                return indegree_norm(x, gctx.in_degree)
            if op.kind == "scatter_gather":
                return gctx.aggregate(x, op.attrs["aggr"])
            if op.kind == "fused_aggregate":
                # norm -> sum -> norm [-> relu] in one op (fuse_norm_
                # aggregate).  The activation sits OUTSIDE the
                # symmetric custom_vjp (relu is nonlinear) but inside
                # this op's fusion scope, so XLA folds it into the
                # aggregation epilogue.
                y = gctx.aggregate_fused(x)
                if op.attrs.get("activation",
                                AC_MODE_NONE) != AC_MODE_NONE:
                    y = dense.activation(y, op.attrs["activation"])
                return y
            if op.kind == "gat":
                return gctx.gat_attention(
                    x, params[f"{op.param}_src"],
                    params[f"{op.param}_dst"],
                    neg_slope=op.attrs["neg_slope"])
            if op.kind in ("typed_input", "rel_linear", "root_linear",
                           "rel_aggregate"):
                return self._eval_typed(op, x, params, gctx)
            if op.kind == "activation":
                return dense.activation(x, op.attrs["mode"])
            if op.kind == "add":
                return vals[op.inputs[0]] + vals[op.inputs[1]]
            if op.kind == "scale_add":
                eps = params[op.param].astype(vals[op.inputs[0]].dtype)
                return vals[op.inputs[0]] + eps * vals[op.inputs[1]]
            if op.kind == "mul":
                return vals[op.inputs[0]] * vals[op.inputs[1]]
            if op.kind == "lerp":
                # the two scalars in float32 whatever the compute dtype
                # (XLA fuses the casts away): as weak-typed factors of
                # bfloat16 arrays they would round to 8 bits, 0.2% off
                # each — a gain error that compounds down a deep stack
                # and that the loss, exponential in the logits' scale,
                # shows tenfold (PERF.md section 6, PR 33)
                al = op.attrs["alpha"]
                a, b = vals[op.inputs[0]], vals[op.inputs[1]]
                return ((1.0 - al) * a.astype(jnp.float32)
                        + al * b.astype(jnp.float32)).astype(a.dtype)
            raise ValueError(f"unknown op kind {op.kind}")

    def loss_fn(self, params: Dict[str, jax.Array], feats: jax.Array,
                labels: jax.Array, mask: jax.Array, gctx: GraphContext,
                key: Optional[jax.Array] = None,
                train: bool = True, remat: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
        """(summed masked CE, logits) — the differentiable objective whose
        gradient equals the reference's ``softmax - onehot`` on train rows
        (``softmax_kernel.cu:19-33``).  ``remat``: :meth:`apply`'s.
        In train mode the op list is :meth:`loss_cut`'s, and the logits
        are the rows the loss reads: every row, or a typed model's
        labelled kind."""
        loss, logits, _ = self._objective(params, feats, labels, mask,
                                          gctx, key, train, remat)
        return loss, logits

    def loss_and_state(self, params: Dict[str, jax.Array],
                       feats: jax.Array, labels: jax.Array,
                       mask: jax.Array, gctx: GraphContext,
                       key: Optional[jax.Array] = None,
                       remat: bool = False
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """The train step's objective with what it moves beside it:
        ``(loss, state)`` — :meth:`loss_fn`'s loss in train mode and
        :meth:`apply_stateful`'s new statistics (empty for a model
        without any), the auxiliary output of the trainers'
        ``value_and_grad``."""
        loss, _, state = self._objective(params, feats, labels, mask,
                                         gctx, key, True, remat)
        return loss, state

    def _objective(self, params, feats, labels, mask, gctx, key, train,
                   remat):
        model = self.loss_cut() if train else self
        logits, state = model.apply_stateful(
            params, feats, gctx, key=key, train=train, remat=remat)
        with jax.named_scope(LOSS_SCOPE):
            loss = masked_softmax_cross_entropy(
                *self.labelled(logits, labels, mask))
        return gctx.psum(loss), logits, state

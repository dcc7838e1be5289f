"""A typed (heterogeneous) graph read off the program's one CSR.

Vertex kinds are contiguous id ranges (``node_types`` gives their
counts in id order); an edge's **relation** is the ordered pair of its
endpoints' kinds, so every ordered pair of kinds with at least one
non-self stored edge is one relation, in ``(src kind, dst kind)``
order.  The file's self edges are not relation edges: a typed model's
root term is its self connection (``models/rgcn.py``).  Nothing here is
listed by the user: no relation names, no per-relation flag.

One relational layer is ``sum_r mean_r(h W_r)``: by linearity ONE
weighted sum over the union edge list, each stored edge ``u -> v`` of
relation ``r`` weighing ``1 / deg_r(v)``.  The two sides of an edge
then live in different index spaces.  Two stackings, by relation:

* the **src stack**: relation ``r``'s block holds one row per vertex
  of its source kind (``src_off[r] + u - lo(src kind)``): what
  ``transform_first`` gathers out of (the products ``h_u W_r``);
* the **dst stack**: one row per vertex of its destination kind
  (``dst_off[r] + v - lo(dst kind)``): what ``gather_first`` sums into
  (the means, before their products).  ``inv_deg`` lives on it.

and four passes, each a destination-major CSR over its own pair of
index spaces (:meth:`TypedGraph.pass_csr`), each stored edge weighing
``inv_deg[dst_off[r] + v - lo]`` in all four:

| pass | sums into | gathers out of | is |
| --- | --- | --- | --- |
| ``tf_fwd`` | vertices ``v`` | src stack ``(r, u)`` | transform_first forward |
| ``tf_bwd`` | src stack ``(r, u)`` | vertices ``v`` | its exact transpose |
| ``gf_fwd`` | dst stack ``(r, v)`` | vertices ``u`` | gather_first forward |
| ``gf_bwd`` | vertices ``u`` | dst stack ``(r, v)`` | its exact transpose |

The backward of a relation is the forward of its *reverse* edges with
the *other* end's degree: the union may be symmetric, a relation is
not, so the transposes are tables of their own and the hand-written
backward is exact for any graph.

Which program runs which relations: the eval program scans the four
passes of the whole graph, every relation.  The loss program's last
layer reads the labelled kind's rows alone (``models/builder.py
Model.loss_cut``) and scans the passes of :meth:`TypedGraph.restrict`:
the same four, over the relations that end in that kind — stacks of
those relations' blocks, the destination vertices the id prefix they
end in, every slot still weighing its edge's own ``1 / deg_r(v)``, the
sorted order of each pass masked out of the whole graph's.

The same choice of side on a homogeneous graph, for a GCN-form layer's
linear and its normalized sum, is :func:`resolve_agg_order`.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

TRANSFORM_FIRST = "transform_first"
GATHER_FIRST = "gather_first"
REL_ORDERS = (TRANSFORM_FIRST, GATHER_FIRST)
# the passes an order runs: (forward, backward)
ORDER_PASSES = {TRANSFORM_FIRST: ("tf_fwd", "tf_bwd"),
                GATHER_FIRST: ("gf_fwd", "gf_bwd")}
# the two sides of a GCN-form layer's linear its sum can run on
# (resolve_agg_order)
LINEAR_FIRST = "linear_first"
AGGREGATE_FIRST = "aggregate_first"


@dataclass
class TypedGraph:
    """The relations of a CSR under ``node_types``, and the non-self
    edge list they were read from (kept for the table builders)."""

    node_types: Tuple[int, ...]
    offsets: np.ndarray              # int64 [K + 1] kind id ranges
    relations: Tuple[Tuple[int, int], ...]   # (src kind, dst kind)
    src_off: np.ndarray              # int64 [R + 1] src-stack blocks
    dst_off: np.ndarray              # int64 [R + 1] dst-stack blocks
    e_src: np.ndarray                # int32 [E'] non-self edges, by dst
    e_dst: np.ndarray                # int32 [E']
    e_rel: np.ndarray                # int8  [E']
    inv_deg: np.ndarray              # float32 [dst stack] 1 / deg_r(v)
    # rows of the destination-vertex space ``v`` lives in: every vertex,
    # or the id prefix a restricted graph's relations end in (restrict)
    dst_nodes: Optional[int] = None
    # pass name -> relation edges a row of the pass sums into
    # (_pass_counts: the plan asks before any table does)
    _counts: Dict[str, np.ndarray] = field(default_factory=dict,
                                           repr=False)
    # pass name -> the permutation that sorts the stored edges by the
    # row the pass sums into, where a key had to be sorted for it
    # (_pass_order: a restricted graph masks it)
    _order: Dict[str, np.ndarray] = field(default_factory=dict, repr=False)
    # restrict(): relation indices -> the restricted graph; and on that
    # graph the one it was cut from with the mask of the edges it kept
    _cuts: Dict[Tuple[int, ...], "TypedGraph"] = field(
        default_factory=dict, repr=False)
    _cut_of: Optional[Tuple["TypedGraph", np.ndarray]] = field(
        default=None, repr=False)

    @property
    def num_nodes(self) -> int:
        return int(self.offsets[-1])

    @property
    def src_rows(self) -> int:
        return int(self.src_off[-1])

    @property
    def dst_rows(self) -> int:
        return int(self.dst_off[-1])

    @property
    def num_edges(self) -> int:
        return int(self.e_src.shape[0])

    def _src_key(self) -> np.ndarray:
        lo = self.offsets[[s for s, _ in self.relations]]
        return (self.src_off[:-1] - lo)[self.e_rel] + self.e_src

    def _dst_key(self) -> np.ndarray:
        lo = self.offsets[[d for _, d in self.relations]]
        return (self.dst_off[:-1] - lo)[self.e_rel] + self.e_dst

    def pass_rows(self, name: str) -> Tuple[int, int]:
        """``(rows summed into, rows gathered out of)`` of pass
        ``name``: the heights of its two index spaces."""
        V = self.num_nodes
        Vd = V if self.dst_nodes is None else self.dst_nodes
        try:
            return {"tf_fwd": (Vd, self.src_rows),
                    "tf_bwd": (self.src_rows, Vd),
                    "gf_fwd": (self.dst_rows, V),
                    "gf_bwd": (V, self.dst_rows)}[name]
        except KeyError:
            raise ValueError(f"unknown relation pass {name!r}") from None

    def _edge_rows(self, name: str, into: bool) -> np.ndarray:
        """Per relation edge, in stored order, the row pass ``name``
        sums it into (``into``) or the row it gathers, in that side's
        index space (:meth:`pass_rows`).  One side is a vertex id as
        stored, the other a stacked key that is computed: ask for the
        side that is needed."""
        self.pass_rows(name)
        if into == (name in ("tf_bwd", "gf_fwd")):
            return self._src_key() if name[:2] == "tf" else self._dst_key()
        return self.e_dst if name[:2] == "tf" else self.e_src

    def pass_edges(self, name: str) -> Tuple[np.ndarray, np.ndarray]:
        """``(into, out_of)``: per relation edge, the row pass ``name``
        sums it into and the row it gathers, in the pass's two index
        spaces (:meth:`pass_rows`), in stored order."""
        return self._edge_rows(name, True), self._edge_rows(name, False)

    def _pass_counts(self, name: str) -> np.ndarray:
        """Relation edges each row of pass ``name`` sums into."""
        if name not in self._counts:
            self._counts[name] = np.bincount(
                self._edge_rows(name, True),
                minlength=self.pass_rows(name)[0])
        return self._counts[name]

    def _pass_order(self, name: str) -> Optional[np.ndarray]:
        """The stable permutation of the stored edges that sorts them
        by the row pass ``name`` sums into, with as little sorting as
        the stored order allows — edges are stored by vertex ``v``, so
        ``tf_fwd`` needs none (None) and ``gf_fwd``'s key (relation,
        ``v``) is sorted by a stable sort of the one-byte relation
        alone.  The other two sort their key once, on the whole graph:
        a restricted graph masks the order of the graph it was cut
        from, whose rows keep their relative order
        (:meth:`restrict`)."""
        if name == "tf_fwd":
            return None
        if name == "gf_fwd":
            return np.argsort(self.e_rel, kind="stable")
        if self._cut_of is not None:
            whole, keep = self._cut_of
            order = whole._pass_order(name)
            return (np.cumsum(keep, dtype=np.int32) - 1)[
                order[keep[order]]]
        if name not in self._order:
            self._order[name] = np.argsort(
                self._edge_rows(name, True),
                kind="stable").astype(np.int32)
        return self._order[name]

    def pass_csr(self, name: str
                 ) -> Tuple[np.ndarray, np.ndarray, int, int]:
        """``(row_ptr, col_idx, num_rows, src_rows)`` of pass ``name``:
        a destination-major CSR whose rows are what the pass sums into
        and whose column ids are what it gathers out of."""
        n_into, n_out = self.pass_rows(name)
        out_of = self._edge_rows(name, False)
        order = self._pass_order(name)
        if order is not None:
            out_of = out_of[order]
        row_ptr = np.zeros(n_into + 1, dtype=np.int64)
        np.cumsum(self._pass_counts(name), out=row_ptr[1:])
        return row_ptr, out_of.astype(np.int32), n_into, n_out

    def slot_weights(self, name: str, idx: np.ndarray,
                     sub_dst: np.ndarray) -> np.ndarray:
        """float32 shaped like ``idx``: the weight of every slot of
        pass ``name``'s width-8 table (``idx [..., seg, 8]`` column
        ids, ``sub_dst [..., seg]`` the rows they sum into), read off
        the table itself: ``inv_deg`` at the slot's dst-stack row.
        Padding slots (dummy column, chunk-padding row) weigh 0."""
        n_into, n_out = self.pass_rows(name)
        row = np.broadcast_to(sub_dst[..., None], idx.shape)
        real = (idx != n_out) & (row != n_into)
        if name[:2] == "gf":
            # the dst stack is one of the pass's own two spaces, its
            # dummy row or column one past ``inv_deg``: the row a
            # gf_fwd sub-row sums into (one weight a sub-row), the
            # column a gf_bwd slot gathers — no key to compute
            table = np.append(self.inv_deg, np.float32(0))
            w = (table[sub_dst][..., None] if name == "gf_fwd"
                 else table[idx])
            return np.where(real, w, np.float32(0))
        row = np.where(real, row, 0).astype(np.int64)
        col = np.where(real, idx, 0).astype(np.int64)
        stacked, v = (col, row) if name == "tf_fwd" else (row, col)
        r = np.searchsorted(self.src_off, stacked, side="right") - 1
        lo = self.offsets[[d for _, d in self.relations]]
        key = (self.dst_off[:-1] - lo)[r] + v
        return np.where(real, self.inv_deg[key], 0.0).astype(np.float32)

    def pass_sub_rows(self, name: str) -> int:
        """Width-8 sub-rows pass ``name``'s table holds before chunk
        padding: what the memory plan charges its tables by, before
        any table exists."""
        return int((-(-self._pass_counts(name) // 8)).sum())

    def restrict(self, rels: Sequence[int]) -> "TypedGraph":
        """The typed graph of the relations ``rels`` alone (increasing
        indices into ``relations``): what a layer sums when only the
        rows of the kinds those relations end in are read — the loss
        program's last layer (``models/builder.py Model.loss_cut``).
        Both stacks hold the kept relations' blocks, in order;
        ``inv_deg`` stays each edge's own ``1 / deg_r(v)`` (a
        relation's in-degree does not change when others go); the
        source side keeps every vertex; the destination-vertex space
        shrinks to the id prefix the kept relations end in
        (``dst_nodes``).  Kept on the graph it was cut from: the plan,
        the tables and the ``plan`` line ask for the same one."""
        rels = tuple(int(r) for r in rels)
        if (not rels or list(rels) != sorted(set(rels))
                or not 0 <= rels[0] <= rels[-1] < len(self.relations)):
            raise ValueError(
                f"restrict: expected increasing indices into "
                f"{len(self.relations)} relations, got {list(rels)}")
        if rels not in self._cuts:
            new = np.full(len(self.relations), -1, dtype=np.int8)
            new[list(rels)] = np.arange(len(rels), dtype=np.int8)
            keep = new[self.e_rel] >= 0
            kept = tuple(self.relations[r] for r in rels)
            src_off, dst_off = _stack_offsets(self.node_types, kept)
            def blocks(on_dst_stack):
                return np.concatenate(
                    [on_dst_stack[self.dst_off[r]:self.dst_off[r + 1]]
                     for r in rels])

            self._cuts[rels] = replace(
                self, relations=kept, src_off=src_off, dst_off=dst_off,
                e_src=self.e_src[keep], e_dst=self.e_dst[keep],
                e_rel=new[self.e_rel[keep]],
                inv_deg=blocks(self.inv_deg),
                dst_nodes=int(self.offsets[
                    1 + max(d for _, d in kept)]),
                _counts={"gf_fwd": blocks(self._pass_counts("gf_fwd"))},
                _order={}, _cuts={}, _cut_of=(self, keep))
        return self._cuts[rels]

    def describe(self) -> List[Dict[str, Any]]:
        """One entry a relation for the run manifest's ``relations``."""
        out = []
        edges = np.bincount(self.e_rel, minlength=len(self.relations))
        for r, (s, d) in enumerate(self.relations):
            inv = self.inv_deg[self.dst_off[r]:self.dst_off[r + 1]]
            out.append({
                "src": s, "dst": d, "edges": int(edges[r]),
                "src_rows": int(self.node_types[s]),
                "dst_rows": int(self.node_types[d]),
                "deg_mean": round(float(edges[r])
                                  / self.node_types[d], 3),
                "deg_max": int(round(1.0 / inv[inv > 0].min()))})
        return out


def parse_kinds(text: str, what: str) -> Tuple[int, ...]:
    """``"3,4,5"`` -> ``(3, 4, 5)``; a malformed list raises
    ``ValueError`` naming ``what``."""
    try:
        out = tuple(int(x) for x in text.split(",") if x.strip() != "")
    except ValueError:
        raise ValueError(f"{what}: expected comma-separated integers, "
                         f"got {text!r}") from None
    if not out or any(x < 0 for x in out):
        raise ValueError(f"{what}: expected non-negative integers, "
                         f"got {text!r}")
    return out


def _stack_offsets(node_types, relations):
    """``(src_off, dst_off)``: where each relation's block starts in
    the two stacks, int64 ``[R + 1]``."""
    return tuple(
        np.concatenate([[0], np.cumsum(
            [node_types[r[end]] for r in relations], dtype=np.int64)])
        for end in (0, 1))


def derive_typed(graph, node_types: Sequence[int]) -> TypedGraph:
    """Read the relations of ``graph`` (``core/graph.py Graph``) under
    ``node_types``: self edges dropped, one relation per ordered pair
    of kinds that occurs, an unseen pair gives none."""
    node_types = tuple(int(n) for n in node_types)
    K = len(node_types)
    offsets = np.zeros(K + 1, dtype=np.int64)
    np.cumsum(node_types, out=offsets[1:])
    if int(offsets[-1]) != graph.num_nodes:
        raise ValueError(
            f"node types {list(node_types)} count {int(offsets[-1])} "
            f"vertices, the graph holds {graph.num_nodes}")
    if any(n <= 0 for n in node_types):
        raise ValueError(f"every node type needs at least one vertex, "
                         f"got {list(node_types)}")
    dst = graph.edge_dst()
    src = graph.col_idx
    keep = src != dst
    src, dst = src[keep], dst[keep]
    ks = (np.searchsorted(offsets, src, side="right") - 1).astype(np.int8)
    kd = (np.searchsorted(offsets, dst, side="right") - 1).astype(np.int8)
    pair = ks.astype(np.int32) * K + kd
    seen = np.flatnonzero(np.bincount(pair, minlength=K * K))
    relations = tuple((int(p) // K, int(p) % K) for p in seen)
    rel_of = np.full(K * K, -1, dtype=np.int8)
    rel_of[seen] = np.arange(len(seen), dtype=np.int8)
    src_off, dst_off = _stack_offsets(node_types, relations)
    typed = TypedGraph(
        node_types=node_types, offsets=offsets, relations=relations,
        src_off=src_off, dst_off=dst_off, e_src=src, e_dst=dst,
        e_rel=rel_of[pair], inv_deg=np.zeros(0, np.float32))
    deg = np.bincount(typed._dst_key(), minlength=int(dst_off[-1]))
    typed.inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1),
                             0.0).astype(np.float32)
    typed._counts["gf_fwd"] = deg        # the pass that sums into them
    return typed


def resolve_rel_order(in_dim: int, out_dim: int, lane_width) -> str:
    """Which side of a relation's mean its product sits on — ONE place
    for the rule, from the layer's two widths: the scan gathers one
    row an edge at the width of whatever it reads, so the order that
    gathers the narrower rows moves fewer bytes (the stacked table or
    carry beside it has as many rows either way: a relation's source
    and destination kinds trade places in its reverse).
    ``lane_width(F)`` is the width the layout really runs ``F`` at
    (``core/ell.py agg_lane_width``: the flat scan pads to 128 lanes).
    ``transform_first`` reads ``out_dim``-wide products,
    ``gather_first`` ``in_dim``-wide inputs; on a tie the mean is
    taken first — the products then write destination rows only and
    no stacked table of products is built for the scan to read."""
    return (TRANSFORM_FIRST
            if lane_width(out_dim) < lane_width(in_dim) else GATHER_FIRST)


def agg_passes(order: str, trained_input: bool) -> int:
    """Sum passes a train step makes for a GCN-form layer: the forward
    and its transpose, which carries the cotangent to whatever
    parameter feeds the summed rows — ``W`` under ``linear_first``,
    an earlier layer's under ``aggregate_first`` with a
    ``trained_input``.  Summed first, an input no parameter precedes
    (the model's features, a dropout of them) takes no gradient, and
    autodiff transposes nothing."""
    return 1 if order == AGGREGATE_FIRST and not trained_input else 2


def resolve_agg_order(in_dim: int, out_dim: int, trained_input: bool,
                      lane_width) -> str:
    """Which side of a GCN-form layer's bias-free linear its normalized
    sum runs on — ONE place for the rule, the homogeneous twin of
    :func:`resolve_rel_order`.  ``Â (X W) = (Â X) W`` exactly: no bias,
    and the degree norms are diagonal.  A pass gathers one row an edge
    at the width the layout runs the summed rows at (``lane_width``,
    ``core/ell.py agg_lane_width``), so a side costs its passes
    (:func:`agg_passes`) times that width: ``linear_first`` two at
    ``out_dim``, ``aggregate_first`` one or two at ``in_dim``.  The sum
    moves first only where that is strictly cheaper; on a tie the
    reference's order stays.  DGL's ``GraphConv`` makes the same
    choice on the widths alone (``W`` first when ``in > out``)."""
    first = agg_passes(AGGREGATE_FIRST, trained_input) * lane_width(in_dim)
    last = agg_passes(LINEAR_FIRST, trained_input) * lane_width(out_dim)
    return AGGREGATE_FIRST if first < last else LINEAR_FIRST

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
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

TRANSFORM_FIRST = "transform_first"
GATHER_FIRST = "gather_first"
REL_ORDERS = (TRANSFORM_FIRST, GATHER_FIRST)
# the passes an order runs: (forward, backward)
ORDER_PASSES = {TRANSFORM_FIRST: ("tf_fwd", "tf_bwd"),
                GATHER_FIRST: ("gf_fwd", "gf_bwd")}


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
    # pass name -> width-8 sub-rows (pass_sub_rows: the plan asks twice)
    _sub_rows: Dict[str, int] = field(default_factory=dict, repr=False)

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
        try:
            return {"tf_fwd": (V, self.src_rows),
                    "tf_bwd": (self.src_rows, V),
                    "gf_fwd": (self.dst_rows, V),
                    "gf_bwd": (V, self.dst_rows)}[name]
        except KeyError:
            raise ValueError(f"unknown relation pass {name!r}") from None

    def pass_edges(self, name: str) -> Tuple[np.ndarray, np.ndarray]:
        """``(into, out_of)``: per relation edge, the row pass ``name``
        sums it into and the row it gathers, in the pass's two index
        spaces (:meth:`pass_rows`), in stored order."""
        self.pass_rows(name)
        vertex = self.e_dst if name[:2] == "tf" else self.e_src
        stacked = self._src_key() if name[:2] == "tf" else self._dst_key()
        return ((vertex, stacked) if name in ("tf_fwd", "gf_bwd")
                else (stacked, vertex))

    def pass_csr(self, name: str
                 ) -> Tuple[np.ndarray, np.ndarray, int, int]:
        """``(row_ptr, col_idx, num_rows, src_rows)`` of pass ``name``:
        a destination-major CSR whose rows are what the pass sums into
        and whose column ids are what it gathers out of."""
        n_into, n_out = self.pass_rows(name)
        into, out_of = self.pass_edges(name)
        if name != "tf_fwd":         # stored order is by vertex ``v``
            order = np.argsort(into, kind="stable")
            out_of = out_of[order]
        row_ptr = np.zeros(n_into + 1, dtype=np.int64)
        np.cumsum(np.bincount(into, minlength=n_into), out=row_ptr[1:])
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
        row = np.where(real, row, 0).astype(np.int64)
        col = np.where(real, idx, 0).astype(np.int64)
        if name == "gf_fwd":
            key = row
        elif name == "gf_bwd":
            key = col
        else:
            stacked, v = (col, row) if name == "tf_fwd" else (row, col)
            r = np.searchsorted(self.src_off, stacked, side="right") - 1
            lo = self.offsets[[d for _, d in self.relations]]
            key = (self.dst_off[:-1] - lo)[r] + v
        return np.where(real, self.inv_deg[key], 0.0).astype(np.float32)

    def pass_sub_rows(self, name: str) -> int:
        """Width-8 sub-rows pass ``name``'s table holds before chunk
        padding: what the memory plan charges its tables by, before
        any table exists."""
        if name not in self._sub_rows:
            into, _ = self.pass_edges(name)
            self._sub_rows[name] = int((-(-np.bincount(
                into, minlength=self.pass_rows(name)[0]) // 8)).sum())
        return self._sub_rows[name]

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
    src_off = np.zeros(len(relations) + 1, dtype=np.int64)
    dst_off = np.zeros(len(relations) + 1, dtype=np.int64)
    np.cumsum([node_types[s] for s, _ in relations], out=src_off[1:])
    np.cumsum([node_types[d] for _, d in relations], out=dst_off[1:])
    typed = TypedGraph(
        node_types=node_types, offsets=offsets, relations=relations,
        src_off=src_off, dst_off=dst_off, e_src=src, e_dst=dst,
        e_rel=rel_of[pair], inv_deg=np.zeros(0, np.float32))
    deg = np.bincount(typed._dst_key(), minlength=int(dst_off[-1]))
    typed.inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1),
                             0.0).astype(np.float32)
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

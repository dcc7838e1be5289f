"""Edge-balanced contiguous vertex-range graph partitioner.

Re-implements the reference's greedy sweep (``gnn.cc:806-829``): walk
vertices in order accumulating in-edge counts; whenever the running count
exceeds ``cap = ceil(E / num_parts)`` close the current range at this
vertex (inclusive) and reset the counter.  The reference then *asserts*
that exactly ``num_parts`` ranges were produced (``gnn.cc:829``) — which
can fail on skewed graphs.  We keep the same greedy semantics but make the
result total: if the sweep closes fewer than ``num_parts`` ranges, the
tail ranges are empty; it can never produce more because the cap
guarantees at least one vertex per closed range.

On top of the ranges we add what the TPU SPMD layer needs and Legion
provided implicitly (``gnn_mapper.cc`` + region partitions): *padded,
equal-sized* shards so every device holds identical static shapes.
Node counts pad to ``max_part_nodes`` rounded up to ``node_multiple``
(sublane-friendly), edge counts to ``max_part_edges`` rounded to
``edge_multiple``.  Padding edges point at a dummy source (node index
``V``, whose feature row is zero) and a dummy destination (the last padded
row), so they aggregate zeros and touch no real output row.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from .graph import Graph


def edge_balanced_bounds(row_ptr: np.ndarray, num_parts: int
                         ) -> List[Tuple[int, int]]:
    """Greedy edge-balanced split into ``num_parts`` contiguous inclusive
    vertex ranges ``[left, right]`` (reference ``gnn.cc:806-829``).
    Ranges may be empty (``left > right``) only in the padded tail.

    The Python fallback is vectorized: the greedy sweep closes a range
    at the first vertex whose running edge count exceeds the cap, i.e.
    at ``searchsorted(row_ptr, row_ptr[left] + cap, 'right') - 1`` —
    O(P log V) instead of the former O(V) degree loop, bit-identical
    to the native sweep (tests/test_native.py test_bounds_parity)."""
    from .. import native
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    num_nodes = row_ptr.shape[0] - 1
    if native.available():
        return [tuple(b) for b in
                native.edge_balanced_bounds(row_ptr, num_parts)]
    num_edges = int(row_ptr[-1])
    cap = (num_edges + num_parts - 1) // num_parts
    bounds: List[Tuple[int, int]] = []
    left = 0
    for _ in range(num_parts - 1):
        if left >= num_nodes:
            break
        # first v with row_ptr[v+1] - row_ptr[left] > cap closes the
        # range at v; v+1 is the first index whose prefix exceeds the
        # target, which searchsorted finds in O(log V)
        v1 = int(np.searchsorted(row_ptr, row_ptr[left] + cap,
                                 side="right"))
        if v1 > num_nodes:
            break  # remaining edges fit under the cap: no more closes
        bounds.append((left, v1 - 1))
        left = v1
    bounds.append((left, num_nodes - 1))
    # pad with empty tail ranges so len(bounds) == num_parts always
    while len(bounds) < num_parts:
        bounds.append((num_nodes, num_nodes - 1))
    return bounds


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# The default shape-quantization multiples: per-part padded node rows
# snap to NODE_MULTIPLE, padded edge slots to EDGE_MULTIPLE.  Named so
# every consumer of the quantization grid — the splitter below, the
# rebalance path, and the program-space auditor's cache-key-drift
# snapping (analysis/programspace.py) — reads the SAME values.
NODE_MULTIPLE = 8
EDGE_MULTIPLE = 128


def quantize_plan_shapes(real_nodes, real_edges,
                         node_multiple: int = NODE_MULTIPLE,
                         edge_multiple: int = EDGE_MULTIPLE
                         ) -> Tuple[int, int]:
    """``(part_nodes, part_edges)`` — the padded per-part shapes a
    plan over these per-part real counts compiles to.  This is THE
    quantized program-shape derivation: :func:`plan_from_bounds` (the
    splitter), the rebalance path, and the program-space auditor
    (``analysis/programspace.py``) all call it, so the shapes the
    trainer actually builds and the shapes the auditor statically
    enumerates can never disagree.

    Includes the full-part padding-edge correction: a part whose real
    rows exactly fill ``part_nodes`` while carrying padding edges
    would absorb dummy-source edges into its last REAL row (the
    sectioned/bdense planners then see out-of-range gathered
    coordinates), so one extra row-multiple is added whenever that
    configuration occurs."""
    real_nodes = np.asarray(real_nodes, dtype=np.int64)
    real_edges = np.asarray(real_edges, dtype=np.int64)
    part_nodes = _round_up(max(int(real_nodes.max()), 1), node_multiple)
    part_edges = _round_up(max(int(real_edges.max()), 1), edge_multiple)
    if any(int(real_nodes[p]) == part_nodes
           and int(real_edges[p]) < part_edges
           for p in range(real_nodes.shape[0])):
        part_nodes += node_multiple
    return part_nodes, part_edges


@dataclass
class PartitionPlan:
    """Partition metadata computable from ``row_ptr`` alone — O(V), no
    edge data.  Each host derives the full plan cheaply (the offsets
    section of a `.lux` is ~8 bytes/vertex) and then loads/builds ONLY
    its own partitions' O(E/P) column data (:func:`partition_col`),
    matching the reference's per-partition loader tasks
    (``load_task.cu:201-245``).

    Conventions:
      - ``part_row_ptr[p]`` is a *local* CSR over the part's padded rows:
        length ``part_nodes + 1``, offsets into the part's padded edge
        slice.  Padding edges attach to the *first padded row* (or the
        last real row when the part has no padded rows) so that edge
        destinations stay contiguous and sorted.  Padding
        edges point at the dummy zero-feature source, so a real last row
        absorbing them just adds zeros.
      - ``node_offset[p]`` is the global id of the part's first row;
        global row ``g`` lives at part ``p``, local row ``g - node_offset[p]``.
    """

    num_nodes: int
    num_edges: int
    num_parts: int
    part_nodes: int              # padded rows per part
    part_edges: int              # padded edges per part
    bounds: List[Tuple[int, int]]
    node_offset: np.ndarray      # int32 [P]
    real_nodes: np.ndarray       # int32 [P] un-padded row counts
    real_edges: np.ndarray       # int64 [P]
    part_row_ptr: np.ndarray     # int32 [P, part_nodes+1] local offsets
    part_in_degree: np.ndarray   # int32 [P, part_nodes] real in-degrees
    # the padding multiples the plan was built with — recorded so a
    # repartition (core/costmodel.py + DistributedTrainer rebalance)
    # re-quantizes to the SAME multiples and repeat shapes hit the
    # compile cache
    node_multiple: int = NODE_MULTIPLE
    edge_multiple: int = EDGE_MULTIPLE

    @property
    def padded_num_nodes(self) -> int:
        """Total rows across all parts (== part_nodes * num_parts)."""
        return self.part_nodes * self.num_parts

    @property
    def dummy_src(self) -> int:
        """Global source id used by padding edges; its feature row must be
        zero."""
        return self.num_nodes

    def edge_range(self, p: int) -> Tuple[int, int]:
        """Global [e0, e1) edge extent of partition ``p``'s real edges
        (parts cover contiguous vertex ranges in order, so their edges
        are consecutive in global CSR order)."""
        e0 = int(self.real_edges[:p].sum())
        return e0, e0 + int(self.real_edges[p])

    def local_to_global(self) -> np.ndarray:
        """int32 [P, part_nodes] map of padded local rows to global node
        ids; padded rows map to ``num_nodes`` (the dummy row)."""
        out = np.full((self.num_parts, self.part_nodes), self.num_nodes,
                      dtype=np.int32)
        for p in range(self.num_parts):
            n = int(self.real_nodes[p])
            out[p, :n] = np.arange(self.node_offset[p],
                                   self.node_offset[p] + n, dtype=np.int32)
        return out

    def global_pad_map(self) -> np.ndarray:
        """int32 [padded_num_nodes] map from concatenated padded rows back
        to global node ids (num_nodes for padding rows).  Used to scatter
        padded-part outputs back to the compact global order."""
        return self.local_to_global().reshape(-1)


@dataclass
class PartitionedGraph(PartitionPlan):
    """A :class:`PartitionPlan` plus every partition's column data —
    the fully materialized form used single-process (multi-host code
    keeps only local parts' columns via :func:`partition_col`).

    ``part_col_idx[p]`` holds *global* source ids; padding edges point
    at the dummy source id ``num_nodes`` (a zero feature row appended
    by the training layer).
    """

    # dataclass default only because the base plan's multiples have
    # defaults; __post_init__ restores the required-field contract
    part_col_idx: np.ndarray = None  # int32 [P, part_edges] global src

    def __post_init__(self):
        if self.part_col_idx is None:
            raise TypeError(
                "PartitionedGraph requires part_col_idx "
                "(materialize_plan attaches it to a plan)")


def padded_edge_list(graph: Graph, multiple: int = 1024
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Single-device analog of the partition padding: return
    ``(edge_src, edge_dst)`` int32 arrays padded to a multiple of
    ``multiple``.  Padding edges use the dummy source ``num_nodes`` (zero
    feature row) and the last real destination row, preserving both the
    aggregation result and the sorted order of the destinations."""
    E = graph.num_edges
    Ep = _round_up(max(E, 1), multiple)
    src = np.full(Ep, graph.num_nodes, dtype=np.int32)
    dst = np.full(Ep, graph.num_nodes - 1, dtype=np.int32)
    src[:E] = graph.col_idx
    dst[:E] = graph.edge_dst()
    return src, dst


def partition_bounds(row_ptr: np.ndarray, num_parts: int,
                     method: str = "greedy",
                     node_multiple: int = NODE_MULTIPLE,
                     edge_multiple: int = EDGE_MULTIPLE,
                     cost_weights=None) -> List[Tuple[int, int]]:
    """Split-point selection — the ONE dispatch between the
    reference's greedy edge sweep (``method='greedy'``) and the
    cost-balanced minimax search (``method='cost'``,
    core/costmodel.py; ``cost_weights`` = the model's
    ``search_weights()``, default the edge-balance prior).  Unknown
    methods raise — a typo must not silently change the split."""
    if method == "greedy":
        return edge_balanced_bounds(row_ptr, num_parts)
    if method == "cost":
        from .costmodel import cost_balanced_bounds
        return cost_balanced_bounds(row_ptr, num_parts,
                                    node_multiple=node_multiple,
                                    edge_multiple=edge_multiple,
                                    weights=cost_weights)
    raise ValueError(f"unknown partition method {method!r}; expected "
                     "'greedy' or 'cost'")


def partition_plan(row_ptr: np.ndarray, num_parts: int,
                   node_multiple: int = NODE_MULTIPLE,
                   edge_multiple: int = EDGE_MULTIPLE,
                   method: str = "greedy",
                   cost_weights=None) -> PartitionPlan:
    """Everything about the partitioning derivable from the global row
    pointers alone (bounds, padded shapes, local row CSRs, degrees) —
    the O(V) metadata every host computes; column data is loaded
    per-partition afterwards (:func:`partition_col`)."""
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    bounds = partition_bounds(row_ptr, num_parts, method=method,
                              node_multiple=node_multiple,
                              edge_multiple=edge_multiple,
                              cost_weights=cost_weights)
    return plan_from_bounds(row_ptr, bounds, num_parts,
                            node_multiple=node_multiple,
                            edge_multiple=edge_multiple)


def plan_from_bounds(row_ptr: np.ndarray, bounds: List[Tuple[int, int]],
                     num_parts: int, node_multiple: int = NODE_MULTIPLE,
                     edge_multiple: int = EDGE_MULTIPLE) -> PartitionPlan:
    """Materialize the plan metadata for explicit ``bounds`` — the
    shared tail of :func:`partition_plan` and the repartitioning path
    (DistributedTrainer.maybe_rebalance hands searched bounds here)."""
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    V = row_ptr.shape[0] - 1
    E = int(row_ptr[-1])
    real_nodes = np.array([max(r - l + 1, 0) for l, r in bounds],
                          dtype=np.int32)
    real_edges = np.array(
        [int(row_ptr[r + 1] - row_ptr[l]) if r >= l else 0
         for l, r in bounds], dtype=np.int64)
    # Padded shapes + the full-part padding-edge correction live in
    # quantize_plan_shapes — the ONE quantized program-shape
    # derivation, shared with the rebalance path and the program-space
    # auditor (analysis/programspace.py).  Latent-bug history of the
    # correction is documented there.
    part_nodes, part_edges = quantize_plan_shapes(
        real_nodes, real_edges, node_multiple, edge_multiple)

    node_offset = np.array([l for l, _ in bounds], dtype=np.int32)
    node_offset = np.minimum(node_offset, V)  # empty tail parts
    part_row_ptr = np.zeros((num_parts, part_nodes + 1), dtype=np.int32)
    part_in_degree = np.zeros((num_parts, part_nodes), dtype=np.int32)
    for p, (l, r) in enumerate(bounds):
        if r < l:
            # empty part: every edge is padding; row 0 absorbs them all.
            part_row_ptr[p, 1:] = part_edges
            continue
        n = r - l + 1
        e0 = int(row_ptr[l])
        local_ptr = (row_ptr[l:r + 2] - e0).astype(np.int32)
        part_row_ptr[p, :n + 1] = local_ptr
        # Padding edges attach immediately after the real edges, on the
        # first padded row (local row n) — or, when n == part_nodes, on
        # the last real row, where they harmlessly add the dummy source's
        # zero feature row.  Every row after that has zero edges, so
        # part_row_ptr[-1] == part_edges always holds.
        part_row_ptr[p, min(n, part_nodes - 1) + 1:] = part_edges
        part_in_degree[p, :n] = np.diff(row_ptr[l:r + 2])
    return PartitionPlan(
        num_nodes=V, num_edges=E, num_parts=num_parts,
        part_nodes=part_nodes, part_edges=part_edges, bounds=bounds,
        node_offset=node_offset, real_nodes=real_nodes,
        real_edges=real_edges, part_row_ptr=part_row_ptr,
        part_in_degree=part_in_degree,
        node_multiple=node_multiple, edge_multiple=edge_multiple)


def partition_col(plan: PartitionPlan, col_slice, p: int) -> np.ndarray:
    """One partition's padded column array (int32 [part_edges], global
    source ids, padding == num_nodes).  ``col_slice(e0, e1)`` returns
    the global ``col_idx[e0:e1]`` — a memory view single-process, a
    seek+read for file-backed hosts — so a host materializes only its
    own partitions' O(E/P) edges (reference ``load_task.cu:201-245``)."""
    out = np.full(plan.part_edges, plan.num_nodes, dtype=np.int32)
    e0, e1 = plan.edge_range(p)
    if e1 > e0:
        out[:e1 - e0] = col_slice(e0, e1)
    return out


def partition_graph(graph: Graph, num_parts: int,
                    node_multiple: int = NODE_MULTIPLE,
                    edge_multiple: int = EDGE_MULTIPLE,
                    method: str = "greedy",
                    cost_weights=None) -> PartitionedGraph:
    """Partition ``graph`` into ``num_parts`` equal-shaped padded
    shards — the fully materialized single-process form (plan + every
    part's columns).  ``method='greedy'`` (default) is the reference's
    edge-balanced sweep; ``method='cost'`` the cost-balanced minimax
    search (core/costmodel.py, ``cost_weights`` as there)."""
    plan = partition_plan(graph.row_ptr, num_parts,
                          node_multiple=node_multiple,
                          edge_multiple=edge_multiple,
                          method=method, cost_weights=cost_weights)
    return materialize_plan(graph, plan)


def materialize_plan(graph: Graph, plan: PartitionPlan
                     ) -> PartitionedGraph:
    """Attach every partition's column data to a plan (single-process;
    the repartitioning path reuses this with searched bounds)."""
    col_slice = lambda e0, e1: graph.col_idx[e0:e1]
    part_col_idx = np.stack([partition_col(plan, col_slice, p)
                             for p in range(plan.num_parts)])
    return PartitionedGraph(**vars(plan), part_col_idx=part_col_idx)

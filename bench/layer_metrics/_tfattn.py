"""Shared by the Graph Transformer cell's readers (``tfattn_ms``,
``tfattn_roofline``): which of the program's attention ops take the
dot-product score, and the bytes and operations one forward of such an
op needs, from shapes alone.

The ops and their shapes come from the program's ``plan`` line (the
``attention`` entries of its manifest's ``resolved`` whose ``score`` is
``"dot"``: heads, head width, the lanes a forward gathers a slot, the
output width), because which table the edges gather, and how wide, is
the program's resolution.  A program whose plan has no such entry (a
parent commit, any other model) gives nothing to read.

The model counts the work whatever implements it, with no reuse of a
gathered row (``roofline.aggregation_bytes``' model: HBM bounds the op
by orders of magnitude):

* per stored edge: the ``[k | v]`` row at its gathered lanes and a
  4-byte index;
* per vertex: the query row read, the output row written and ``2 K``
  float32 statistics (row max, denominator);
* ``4 E K d`` FLOP: the scores and the weighted sum, a multiply and an
  add per element each.
"""

INDEX_BYTES = 4
STAT_BYTES = 4


def dot_entries(run):
    """The plan's ``attention`` entries with ``score: "dot"``, in op
    order; [] when there are none."""
    entries = (run.scratch.get("resolved") or {}).get("attention") or []
    return [e for e in entries if e.get("score") == "dot"]


def forward_bytes(num_edges, num_nodes, entry, itemsize):
    width = entry["heads"] * entry["head_width"]
    return (num_edges * (entry["gather_lanes_fwd"] * itemsize
                         + INDEX_BYTES)
            + num_nodes * ((width + entry["out_width"]) * itemsize
                           + 2 * entry["heads"] * STAT_BYTES))


def forward_flops(num_edges, entry):
    return 4.0 * num_edges * entry["heads"] * entry["head_width"]


def rows(run, ways):
    """``[(op, way, ms), ...]``: the ``agg`` rows of the step-scope
    reduction that belong to the dot-product attention ops, in the
    directions ``ways``; None when there is nothing to read."""
    ops = {e["op"] for e in dot_entries(run)}
    if not ops:
        return None
    got = run.cell.module("layer_metrics", "_step_scopes").measure(run)
    if got is None:
        return None
    return [(i, way, ms) for cls, i, way, ms, _ in got["rows"]
            if cls == "agg" and i in ops and way in ways]

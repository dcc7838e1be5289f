"""``rel_agg_roofline`` (``aggregation`` layer, %): the least time the
chip could take for the forward of the widest relation aggregation of a
typed model — ``_relations.relation_aggregation_bytes`` over the HBM
peak of ``peaks.json``: per stored relation edge one row at the width
the model gathers (its own width, not the lanes a layout pads it to),
a 4-byte index and its 4-byte weight, per row the pass sums into one
write — over what that op's forward takes inside the train step: its
``agg`` ``fwd`` row in ``_step_scopes.py``'s reduction of the device
trace.  The shapes come from the program's ``plan`` line
(``rel_layers``: the gathered width, the stacked rows;
``relation_edges``): which side of the mean the product sits on
decides which width is gathered and which rows are written, and that is
the program's resolution, not the reader's.  HBM bounds the op (2 FLOP
an edge element).  A program that resolves no relation gives nothing to
read."""


def read(run):
    rel = run.cell.module("layer_metrics", "_relations")
    layers, resolved = rel.relation_layers(run)
    got = run.cell.module("layer_metrics", "_step_scopes").measure(run)
    if not layers or got is None or run.peaks is None:
        return None
    widest = max(layers, key=lambda l: l["gather_width"])
    rows = [ms for cls, i, way, ms, _ in got["rows"]
            if cls == "agg" and way == "fwd" and i == widest["op"] and ms]
    if not rows:
        return None
    import jax.numpy as jnp
    itemsize = int(jnp.dtype(run.trainer.compute).itemsize)
    out_rows = (widest["stacked_rows"]
                if widest["rel_order"] == "gather_first"
                else sum(resolved["node_types"]))
    least_ms = rel.relation_aggregation_bytes(
        resolved["relation_edges"], widest["gather_width"], itemsize,
        out_rows) / run.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / rows[0]

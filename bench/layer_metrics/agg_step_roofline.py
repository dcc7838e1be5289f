"""``agg_step_roofline`` (``aggregation`` layer, %): the least time the
chip could take for one forward sum aggregation at the model's widest
aggregated width — ``roofline.aggregation_bytes`` over the peaks of
``peaks.json``, the no-reuse byte model ``agg_roofline`` uses, unchanged
— over what such an op takes inside the train step: the median ``agg``
forward row, in ``_step_scopes.py``'s reduction of the device trace, of
the sum-aggregating ops (``fused_aggregate``, ``scatter_gather``) of
that width.  The step's own time, not a side program's (``agg_roofline``
times the op jitted alone): a deep model has sixteen such rows, and the
median is the op as its neighbours leave it.  HBM bounds it; a fused
epilogue booked to the aggregation (``_step_scopes.py``) only lowers
the share.  One-chip trainers only (a partition's edge count is not the
graph's); a program without instruction scopes gives nothing to read."""

import statistics

SUM_KINDS = ("fused_aggregate", "scatter_gather")


def read(run):
    tr = run.trainer
    ops = getattr(getattr(tr, "model", None), "_ops", None)
    if ops is None or getattr(tr, "gctx", None) is None \
            or run.peaks is None:
        return None
    sums = {i: op for i, op in enumerate(ops) if op.kind in SUM_KINDS}
    got = run.cell.module("layer_metrics", "_step_scopes").measure(run)
    if not sums or got is None:
        return None
    width = max(op.dim for op in sums.values())
    rows = [ms for cls, i, way, ms, _ in got["rows"]
            if cls == "agg" and way == "fwd" and ms
            and i in sums and sums[i].dim == width]
    if not rows:
        return None
    import jax.numpy as jnp
    import roofline
    edges = int(run.data.col_idx.shape[0])
    nodes = int(run.data.row_ptr.shape[0] - 1)
    itemsize = int(jnp.dtype(tr.compute).itemsize)
    least_ms = roofline.least_seconds(
        roofline.aggregation_bytes(edges, nodes, width, itemsize),
        2.0 * edges * width, run.peaks) * 1e3
    return 100.0 * least_ms / statistics.median(rows)

"""``softagg_roofline`` (``aggregation`` layer, %): the least time the
chip could take for the forward of one softmax-weighted aggregation —
``_softagg.soft_aggregation_bytes`` over the HBM peak of
``peaks.json``: per stored edge a row at the gathered lanes and a
4-byte index, per vertex the summed rows and what ``roc.sagg.weights``
reads and writes — over what such an op's forward takes inside the
train step: the median ``agg`` ``fwd`` row of the softmax aggregations
in ``_step_scopes.py``'s reduction of the device trace
(``agg_step_roofline``'s shape: the step's own time, the op as its
neighbours leave it).  HBM bounds it (a few FLOP a gathered element).
Lanes, passes and the table's dtype are the program's (``plan`` line).
One-chip trainers only; a program whose plan has no ``soft_aggregate``
gives nothing to read."""

import statistics


def read(run):
    sa = run.cell.module("layer_metrics", "_softagg")
    soft = sa.plan(run, "soft_aggregate")
    rows = sa.soft_rows(run, ("fwd",))
    if not rows or run.peaks is None \
            or getattr(run.trainer, "gctx", None) is None:
        return None
    times = [ms for _, _, ms in rows if ms]
    if not times:
        return None
    import jax.numpy as jnp
    least_ms = sa.soft_aggregation_bytes(
        int(run.data.col_idx.shape[0]),
        int(run.data.row_ptr.shape[0] - 1), soft["width"],
        soft["gather_lanes_fwd"], soft["passes_fwd"],
        int(jnp.dtype(run.trainer.compute).itemsize),
        int(jnp.dtype(soft["e_dtype"]).itemsize),
    ) / run.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / statistics.median(times)

"""``bn_roofline`` (``model`` layer, %): the least time the chip could
take for a train step's batch normalizations —
``_softagg.batch_norm_bytes`` over the HBM peak of ``peaks.json``:
eight passes over ``[rows counted, width]`` at the compute dtype an op
(forward: ``x`` twice, ``y``; backward: ``x`` and ``g`` twice each,
``dx``), times the ops the ``plan`` line counts — over ``bn_ms``.  HBM
bounds it (a handful of FLOP an element).  Fusions that fold a
neighbouring ReLU or dropout into a pass only lower the share.  A
program whose plan has no ``batch_norm`` gives nothing to read."""


def read(run):
    sa = run.cell.module("layer_metrics", "_softagg")
    bn = sa.plan(run, "batch_norm")
    ms = run.cell.module("layer_metrics", "bn_ms").read(run)
    if bn is None or not ms or run.peaks is None:
        return None
    import jax.numpy as jnp
    itemsize = int(jnp.dtype(run.trainer.compute).itemsize)
    least_ms = sa.batch_norm_bytes(
        bn["rows_counted"], bn["width"], itemsize,
        bn["count"]) / run.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms

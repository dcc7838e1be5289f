"""``tfattn_roofline`` (``aggregation`` layer, %): the least time the
chip could take for one forward of the widest dot-product attention op
(``_tfattn.py``: bytes and operations from shapes alone, over the
peaks of ``peaks.json``; HBM bounds it) over that op's measured forward
time inside the train step: its ``agg`` forward row in
``_step_scopes.py``'s reduction of the device trace.  A program whose
plan has no such op gives nothing to read."""


def read(run):
    tf = run.cell.module("layer_metrics", "_tfattn")
    entries = tf.dot_entries(run)
    got = tf.rows(run, ("fwd",))
    if not entries or not got or run.peaks is None:
        return None
    entry = max(entries, key=lambda e: e["heads"] * e["head_width"])
    fwd_ms = sum(ms for i, _, ms in got if i == entry["op"])
    if not fwd_ms:
        return None
    import jax.numpy as jnp
    import roofline
    least_s = roofline.least_seconds(
        tf.forward_bytes(int(run.data.col_idx.shape[0]),
                         int(run.data.row_ptr.shape[0] - 1), entry,
                         int(jnp.dtype(run.trainer.compute).itemsize)),
        tf.forward_flops(int(run.data.col_idx.shape[0]), entry),
        run.peaks)
    return 100.0 * least_s * 1e3 / fwd_ms

"""``attn_roofline`` (``aggregation`` layer, %): the least time the chip
could take for one forward of the model's widest attention op
(``_attention.py``: bytes and operations from shapes alone, over the
peaks of ``peaks.json``; HBM bounds it), over that op's measured forward
time inside the train step: the ``agg`` forward row of the op in
``_step_scopes.py``'s reduction of the device trace."""


def read(run):
    model = run.cell.module("layer_metrics", "_attention")
    entry = model.widest_op(run)
    got = run.cell.module("layer_metrics", "_step_scopes").measure(run)
    if entry is None or got is None:
        return None
    fwd_ms = sum(ms for cls, i, way, ms, _ in got["rows"]
                 if (cls, i, way) == ("agg", entry["op"], "fwd"))
    least = model.least_ms(run, entry)
    if not fwd_ms or least is None:
        return None
    return 100.0 * least / fwd_ms

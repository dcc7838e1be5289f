"""``softagg_ms`` (``aggregation`` layer, ms): device self time per
epoch, inside the train step, under the ``roc.agg.op<i>`` scopes of the
softmax-weighted aggregations (the ops the ``plan`` line's
``soft_aggregate`` lists) — the chunk scans and the ``roc.sagg.weights``
arithmetic around them together, forward and backward.  Source:
``_step_scopes.py``'s rows.  On this model it is ``step_agg_ms`` (every
aggregating op is one of these); the two would part in a model that
mixes aggregations.  A program whose plan has no ``soft_aggregate``
gives nothing to read."""


def read(run):
    rows = run.cell.module("layer_metrics", "_softagg").soft_rows(
        run, ("fwd", "bwd", "recompute"))
    if not rows:
        return None
    return sum(ms for _, _, ms in rows)

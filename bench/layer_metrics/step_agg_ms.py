"""``step_agg_ms`` (``aggregation`` layer, ms): device self time per
epoch, inside the train step, of the operations under a ``roc.agg``
program scope and not under ``roc.halo`` — every aggregating model op,
forward and backward, index preparation included.  Source: the device
trace joined to the compiled program's own text — see
``_step_scopes.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_step_scopes").class_ms(
        run, ("agg",))

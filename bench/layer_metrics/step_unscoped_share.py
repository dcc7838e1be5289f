"""``step_unscoped_share`` (``step_loop`` layer, %): the share of the
train step's device self time whose instruction carries no ``roc.``
program scope — the tracing's own coverage, so that ``step_agg_ms`` and
``step_model_ms`` cannot shrink in silence.  Source: the device trace
joined to the compiled program's own text — see ``_step_scopes.py``."""


def read(run):
    helper = run.cell.module("layer_metrics", "_step_scopes")
    got = helper.measure(run)
    if got is None or not got["step_ms"]:
        return None
    return 100.0 * got["by_class"].get(helper.UNSCOPED, 0.0) / got["step_ms"]

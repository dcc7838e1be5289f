"""``agg_ms`` (``aggregation`` layer, ms): one forward + one vjp of the
model's own aggregation op, jitted alone — see ``_aggregation.py``."""


def read(run):
    got = run.cell.module("layer_metrics", "_aggregation").measure(run)
    return None if got is None else got["forward_vjp_ms"]

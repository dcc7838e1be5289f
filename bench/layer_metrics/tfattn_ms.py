"""``tfattn_ms`` (``aggregation`` layer, ms): device self time per
epoch, inside the train step, under the ``roc.agg.op<i>`` scopes of the
dot-product attention ops (the plan line's ``attention`` entries with
``score: "dot"``) — the forward, both passes of the backward (pass A
for the queries, pass B for the keys and values) and the gate, together.
Source: ``_step_scopes.py``'s rows.  The projections are ``linear`` ops
of their own (``roc.dense``) and are not in it.  A program whose plan
has no such op gives nothing to read."""


def read(run):
    got = run.cell.module("layer_metrics", "_tfattn").rows(
        run, ("fwd", "bwd", "recompute"))
    if not got:
        return None
    return sum(ms for _, _, ms in got)

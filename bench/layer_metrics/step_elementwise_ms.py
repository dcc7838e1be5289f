"""``step_elementwise_ms`` (``model`` layer, ms): device self time per
epoch, inside the train step, under the ``roc.dense.op<i>.<kind>``
scopes of the model ops that are not ``linear`` — ``dropout``, ``lerp``,
``add``, ``activation`` and the like — forward, backward and, under
remat, recompute.  In a deep narrow model these passes over ``[V, h]``,
not the matrix products, are the dense share of the step.  Source:
``_step_scopes.py``'s rows (class, op index, direction); the kind of op
``i`` is read off the live trainer's op list.  XLA books a fusion to one
of the operations fused into it, so a ``lerp`` folded into a matrix
product's epilogue is counted with the product: a boundary error, as in
``step_model_ms``.  Readers that share ``_step_scopes.measure`` share
one reduction of the trace and one ``step_scopes`` line.  A program
without instruction scopes gives nothing to read."""


def read(run):
    ops = getattr(getattr(run.trainer, "model", None), "_ops", None)
    got = run.cell.module("layer_metrics", "_step_scopes").measure(run)
    if ops is None or got is None:
        return None
    return sum(ms for cls, i, _way, ms, _ in got["rows"]
               if cls == "dense" and i is not None and i < len(ops)
               and ops[i].kind != "linear")

"""``bn_ms`` (``model`` layer, ms): device self time per epoch, inside
the train step, under the ``roc.dense.op<i>.batch_norm`` program scopes
— every batch normalization's forward (the ``roc.bn.stats`` sums and the
normalization), backward (its two sums and ``dx``) and, under remat,
recompute.  Source: ``_relations.named_scope_ms`` on the scope's name.
XLA books a fusion to one of the operations fused into it, so a ReLU or
a dropout folded into the normalization's pass is counted with it: a
boundary error, as in ``step_model_ms``.  A program without the scope
gives nothing to read."""


def read(run):
    return run.cell.module("layer_metrics", "_relations").named_scope_ms(
        run, ".batch_norm")

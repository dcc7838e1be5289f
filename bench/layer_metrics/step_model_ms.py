"""``step_model_ms`` (``model`` layer, ms): device self time per epoch,
inside the train step, of the operations under the ``roc.dense``,
``roc.loss`` and ``roc.opt`` program scopes — linears, dropout,
activations, the loss, the Adam update and the parameter casts, forward
and backward.  Source: the device trace joined to the compiled
program's own text — see ``_step_scopes.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_step_scopes").class_ms(
        run, ("dense", "loss", "opt"))

"""``opt_embed_ms`` (``model`` layer, ms): device self time per epoch,
inside the train step, under the ``roc.opt.embed`` program scope — the
embedding tables' Adam update, their casts to the compute dtype and the
casts' transposes (the gradient's way back to fp32): the optimizer's
stream over the tables, apart from the weights' (``roc.opt`` holds
both; ``step_model_ms`` counts both).  Source: ``_relations.
named_scope_ms``.  A program without the scope gives nothing to
read."""


def read(run):
    return run.cell.module("layer_metrics", "_relations").named_scope_ms(
        run, "roc.opt.embed")

"""``attn_gather_ms`` (``aggregation`` layer, ms): device self time per
epoch, inside the train step, of the weighted gather of every attention
op, forward and backward — the ``gather`` phase (the feature gather over
the edges, the softmax-weighted sum and the division).  Source: the
device trace joined to the compiled program's own text — see
``_attention_phases.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_attention_phases").phase_ms(
        run, ("gather",))

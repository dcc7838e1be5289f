"""``attn_softmax_ms`` (``aggregation`` layer, ms): device self time per
epoch, inside the train step, of the edge softmax of every attention op,
forward and backward — the ``scores`` and ``stats`` phases (per-vertex
score projections, their gather over the edges, LeakyReLU, row max,
``exp``, denominator).  Source: the device trace joined to the compiled
program's own text — see ``_attention_phases.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_attention_phases").phase_ms(
        run, ("scores", "stats"))

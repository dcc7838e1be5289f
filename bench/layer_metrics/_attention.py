"""Attention aggregation: the operations and bytes the algorithm needs,
from shapes alone, and which of the model's attention ops is the widest.
Shared by ``attn_roofline``; the phase times are ``_attention_phases.py``.

The model counts the same work whatever implements it (one pass or
three over the edges, buckets or chunks, padding or none): for ``K``
heads of width ``d`` over ``E`` stored edges and ``V`` vertices,

* per stored edge: one source row of ``z`` (``K * d * itemsize``
  bytes), that source's ``K`` scores (``K * score_itemsize``) and one
  4-byte index;
* per vertex: one read and one write of the output row (``2 * K * d *
  itemsize``) and its ``K`` destination scores (``K *
  score_itemsize``);
* ``2 * E * K * d`` FLOP (a multiply and an add per gathered element).

No reuse of a gathered row is assumed, as in ``roofline.
aggregation_bytes``: HBM bounds the op by three orders of magnitude, and
a layout that reuses rows from fast memory may pass 100%.
"""

SCORE_ITEMSIZE = 4        # the program keeps scores in float32


def attention_bytes(num_edges, num_nodes, heads, head_width, itemsize,
                    score_itemsize=SCORE_ITEMSIZE):
    row = heads * head_width * itemsize
    scores = heads * score_itemsize
    return (num_edges * (row + scores + 4)
            + num_nodes * (2 * row + scores))


def attention_flops(num_edges, heads, head_width):
    return 2.0 * num_edges * heads * head_width


def widest_op(run):
    """The ``attention`` entry (``plan`` line: the run manifest's
    ``resolved``) of the widest attention op, the first of equals; None
    when the program resolved none (a model without attention, or a
    program from before the entry existed)."""
    entries = (run.scratch.get("resolved") or {}).get("attention") or []
    if not entries:
        return None
    return max(entries, key=lambda e: e["heads"] * e["head_width"])


def least_ms(run, entry):
    """The least time one forward of ``entry``'s op can take on this
    chip; None without the chip's peaks (a rehearsal)."""
    if run.peaks is None:
        return None
    import roofline
    import jax.numpy as jnp
    E = int(run.data.col_idx.shape[0])
    V = int(run.data.row_ptr.shape[0] - 1)
    itemsize = int(jnp.dtype(run.trainer.compute).itemsize)
    nbytes = attention_bytes(E, V, entry["heads"], entry["head_width"],
                             itemsize)
    flops = attention_flops(E, entry["heads"], entry["head_width"])
    return roofline.least_seconds(nbytes, flops, run.peaks) * 1e3

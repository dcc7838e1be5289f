"""GAT model family (graph attention networks).

The reference has no attention model — its only aggregation is the
unweighted CSR sum (``scattergather_kernel.cu:20-76``).  GAT is the
framework's TPU-native extension, showing the op set generalizes past
the reference's fixed GCN stack: the additive attention of Velickovic
et al. (ICLR'18), multi-head with concatenation on the hidden layers,
expressed with the builder ops::

    t = dropout(t, rate)                       # input_dropout on layer 1
    z = linear(t, layers[i], AC_MODE_NONE)     # z = t W
    a = gat_attention(z, heads)                # softmax-weighted sum
    if skip: a = add(a, linear(t, layers[i]))  # + t R, no bias
    if not last: a = elu(a) | relu(a)

With ``skip=True, activation="relu"`` and an ``input_dropout`` of its
own this is the full-batch GAT of the OGB ogbn-arxiv leaderboard (DGL
``examples/pytorch/ogb/ogbn-arxiv``: every ``GATConv`` carries a
bias-free ``res_fc``), less its BatchNorm, which the op set lacks,
and less the bias of each ``GATConv``: ``ops/dense.py linear`` takes a
bias since the typed models needed one (``models/rgcn.py``), the
builder's ``linear`` op still passes none.

The edge softmax runs on one of two layouts (ops/attention.py has both
mechanisms): the degree-bucketed ELL tables (``aggr_impl='ell'``, every
row's whole neighborhood in one bucket row) or the uniform width-8
flat tables (``'attn_flat8'``).  ``train/trainer.py
resolve_attention_impl`` picks between them by edge count.
"""

from __future__ import annotations

from typing import Optional, Sequence

from .builder import Model
from ..ops.dense import AC_MODE_NONE

ACTIVATIONS = ("elu", "relu")


def build_gat(layers: Sequence[int], dropout_rate: float = 0.5,
              neg_slope: float = 0.2, heads: int = 1,
              skip: bool = False, activation: str = "elu",
              input_dropout: Optional[float] = None) -> Model:
    """``heads`` applies to the hidden layers (multi-head concat —
    each hidden dim must divide by it); the output layer is always
    single-head, as in the paper.  ``skip`` adds a bias-free linear
    map of each layer's (dropped) input to its attention output,
    output layer included.  ``input_dropout`` is the rate on the raw
    features (None: ``dropout_rate``, as on every other layer)."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"build_gat: activation {activation!r} not in "
                         f"{ACTIVATIONS}")
    model = Model(in_dim=layers[0])
    t = model.input()
    n = len(layers)
    for i in range(1, n):
        last = i == n - 1
        rate = (input_dropout if i == 1 and input_dropout is not None
                else dropout_rate)
        t = model.dropout(t, rate)
        a = model.gat_attention(
            model.linear(t, layers[i], AC_MODE_NONE),
            neg_slope=neg_slope, heads=1 if last else heads)
        if skip:
            a = model.add(a, model.linear(t, layers[i], AC_MODE_NONE))
        if not last:
            a = model.elu(a) if activation == "elu" else model.relu(a)
        t = a
    model.softmax_cross_entropy(t)
    return model

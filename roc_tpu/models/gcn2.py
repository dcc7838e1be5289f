"""GCNII model family: deep GCN via initial residual + identity
mapping (Chen, Wei, Huang, Ding, Li, *Simple and Deep Graph
Convolutional Networks*, ICML 2020, arXiv:2007.02133).

With ``P = D^-1/2 (A + I) D^-1/2`` over the stored graph (self edges
pre-added — the reference's GCN normalization, ``gnn.cc:78-91``),
``H_0 = relu(dropout(X) W_in)``, ``Ĥ = dropout(H_{l-1})`` and
``beta_l = log(lam / l + 1)`` decaying over depth, layer ``l``
(1-indexed) has two forms.

**GCNII** (the paper's eq. 5; ``star=False``, the default) — one
weight a layer, shared by the propagated and the initial-residual
branch::

    M_l = (1 - alpha) P Ĥ + alpha H_0             # initial residual
    H_l = relu((1 - beta_l) M_l + beta_l M_l W_l)   # identity map

**GCNII-star**, written GCNII* (``star=True``; the paper's GCNII* variant, section 3's
closing remark, as the authors' code runs it on the OGB ogbn-arxiv
leaderboard: ``github.com/chennnM/GCNII``, ``PyG/ogbn-arxiv/``,
``GCNIIdenseConv`` — ``support = (1-beta)(1-alpha) x + beta x W1``,
``initial = (1-beta) alpha h0 + beta h0 W2``, ``out = P support +
initial``) — separate weights for the two branches::

    H_l = relu( (1 - beta_l) [ (1 - alpha) P Ĥ + alpha H_0 ]
                + beta_l [ (P Ĥ) W1_l + H_0 W2_l ] )

The paper writes the second bracket with the constants in it,
``(1 - alpha) P Ĥ W1 + alpha H_0 W2``; the authors' code absorbs
``(1 - alpha)`` and ``alpha`` into ``W1_l`` and ``W2_l``, and so does
this one.  ``P`` is linear, so ``P (Ĥ W1) = (P Ĥ) W1``: the
aggregation runs once a layer, on ``Ĥ``, and both brackets read it.

The two mechanisms are what lets GCNII stack 16-64 layers without
oversmoothing, where the reference's plain stack degrades past ~4
(its deep-stack answer is the dense residual, ``gnn.cc:86-90``).
The reference has no such model; GCNII completes the zoo's deep end.

Every combine is the builder's fixed-scalar ``lerp`` op (and ``add``
for the starred form's two products), so a layer is GCN's hot
aggregation path plus one (two) extra [V, H] matmuls — XLA fuses the
lerps into their producers.

``layers`` follows the CLI convention ``F-H-...-H-C``: layers[0] is
the input feature dim, layers[-1] the class count, and each
intermediate entry one GCNII layer (all must share one width H — the
initial residual adds H_0 into every layer).  Parameters, in
construction order: ``linear_0`` = ``W_in``; then ``W_l`` a layer
(``W1_l``, ``W2_l`` a layer when starred); last ``W_out``.
"""

from __future__ import annotations

import math
from typing import Sequence

from .builder import Model
from ..ops.dense import AC_MODE_NONE


def build_gcn2(layers: Sequence[int], alpha: float = 0.1,
               lam: float = 0.5,
               dropout_rate: float = 0.5,
               star: bool = False) -> Model:
    if len(layers) < 3:
        raise ValueError(
            "GCNII needs at least one hidden layer (F-H-C); for a "
            "propagation-free linear model use --model sgc")
    hidden = layers[1]
    if any(h != hidden for h in layers[1:-1]):
        raise ValueError(
            f"GCNII hidden widths must all match (the initial "
            f"residual adds H_0 into every layer), got {layers[1:-1]}")
    if not 0.0 <= alpha <= 1.0:
        raise ValueError(f"alpha must be in [0, 1], got {alpha}")
    if lam <= 0.0:
        raise ValueError(f"lam must be > 0, got {lam}")
    model = Model(in_dim=layers[0])
    t = model.input()
    # input projection -> H_0
    t = model.dropout(t, dropout_rate)
    t = model.linear(t, hidden, AC_MODE_NONE)
    t = model.relu(t)
    h0 = t
    n_layers = len(layers) - 2
    for l in range(1, n_layers + 1):
        beta = math.log(lam / l + 1.0)
        t = model.dropout(t, dropout_rate)
        t = model.indegree_norm(t)
        t = model.scatter_gather(t)
        t = model.indegree_norm(t)
        m = model.lerp(t, h0, alpha)          # initial residual
        if star:
            w = model.add(model.linear(t, hidden, AC_MODE_NONE),
                          model.linear(h0, hidden, AC_MODE_NONE))
        else:
            w = model.linear(m, hidden, AC_MODE_NONE)
        t = model.lerp(m, w, beta)            # identity mapping
        t = model.relu(t)
    t = model.dropout(t, dropout_rate)
    t = model.linear(t, layers[-1], AC_MODE_NONE)
    model.softmax_cross_entropy(t)
    return model

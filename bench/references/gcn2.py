"""Plain forward of GCNII and GCNII* (Chen, Wei, Huang, Ding, Li,
*Simple and Deep Graph Convolutional Networks*, ICML 2020,
arXiv:2007.02133) as the OGB ogbn-arxiv leaderboard's GCNII rows run
the starred form (the authors' ``github.com/chennnM/GCNII``,
``PyG/ogbn-arxiv/``), in inference mode (dropout is the identity).
With ``P = D^-1/2 A D^-1/2`` over the stored graph (which holds every
self edge, so ``A`` is the paper's ``A + I``) and
``beta_l = log(lam / l + 1)``::

    H_0 = relu(X W_in)                                      W_in [F, h]
    H_l = relu( (1 - beta_l) [ (1 - alpha) P H_{l-1} + alpha H_0 ]
                + beta_l [ (P H_{l-1}) W1_l + H_0 W2_l ] )  l = 1 .. L
    logits = H_L W_out                                      W_out [h, C]

when ``model["variant"]`` is ``"gcn2star"``.  This is the authors'
``GCNIIdenseConv`` (``support = (1-beta)(1-alpha) x + beta x W1``,
``initial = (1-beta) alpha h0 + beta h0 W2``, ``out = P support +
initial``): the paper's GCNII* equation, whose second bracket reads
``(1 - alpha) P H W1 + alpha H_0 W2``, with the constants ``(1 -
alpha)`` and ``alpha`` absorbed into ``W1_l`` and ``W2_l``.  The
script's BatchNorm and the biases of its input and output layers are
absent from the program and so from here (``configs/gcn2-arxiv.json``,
``assumed``).  Any other variant is the paper's GCNII, one weight a
layer::

    M_l = (1 - alpha) P H_{l-1} + alpha H_0
    H_l = relu((1 - beta_l) M_l + beta_l M_l W_l)

``layers`` is the CLI's ``-layers`` list: input width first, classes
last, one entry a layer between.  Parameters are the program's
``linear_<k>`` in construction order: ``W_in``, then ``W1_l``, ``W2_l``
(or ``W_l``) layer by layer, then ``W_out``.

A reference that needs more than ``reference.aggregate_sum`` / ``dense``
brings it in its own file, as ``references/gat.py`` does; this one needs
nothing more.  ``forward`` is differentiable; :func:`loss_and_grads` is
its training twin for the tests (the program's objective: cross-entropy
summed over the train rows).  ``forward``'s two keywords exist for
``probes/gcn2_precision.py`` only, which swaps in a neighbour sum of a
lower precision (``propagate``) and rounds what a program under test
would store (``stored``): the reference proper is float32 throughout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import aggregate_sum, dense

TRAIN = 1                      # the program's MASK_TRAIN


def inv_sqrt_degree(graph):
    return jnp.where(graph.degree > 0,
                     1.0 / jnp.sqrt(jnp.maximum(graph.degree, 1.0)), 0.0)


def propagate_sum(h, graph):
    """``P h`` with ``P = D^-1/2 A D^-1/2`` over the stored edges."""
    d = inv_sqrt_degree(graph)
    return aggregate_sum(h * d[:, None], graph) * d[:, None]


def forward(params, x, graph, model, propagate=propagate_sum,
            stored=lambda a: a):
    n_layers = len(model["layers"]) - 2
    alpha, lam = float(model["alpha"]), float(model["lam"])
    star = model.get("variant") == "gcn2star"
    h0 = stored(jax.nn.relu(dense(stored(x), stored(params["linear_0"]))))
    h, k = h0, 1
    for l in range(1, n_layers + 1):
        beta = math.log(lam / l + 1.0)
        p = stored(propagate(h, graph))
        m = (1.0 - alpha) * p + alpha * h0
        if star:
            w = (stored(dense(p, stored(params[f"linear_{k}"])))
                 + stored(dense(h0, stored(params[f"linear_{k + 1}"]))))
            k += 2
        else:
            w = stored(dense(stored(m), stored(params[f"linear_{k}"])))
            k += 1
        h = stored(jax.nn.relu((1.0 - beta) * m + beta * w))
    return dense(h, stored(params[f"linear_{k}"]))


def loss_and_grads(params, x, labels, mask, graph, model):
    """``(loss, d loss / d params)`` of the training objective on the
    reference's own logits: the cross-entropy summed over the train
    rows."""

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, x, graph, model), axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(mask == TRAIN, ll, 0.0))

    return jax.value_and_grad(loss)(params)

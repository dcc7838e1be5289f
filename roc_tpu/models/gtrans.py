"""Graph Transformer model family: the Graph Transformer layer of UniMP
(Shi, Huang, Feng, Zhong, Wang, Sun, *Masked Label Prediction: Unified
Message Passing Model for Semi-Supervised Classification*, IJCAI 2021,
arXiv:2009.03509) with the equations of PyTorch Geometric's
``TransformerConv(..., beta=True)``, stacked as its ogbn-arxiv rows run
it full batch: LayerNorm then ReLU between layers, the hidden layers'
heads concatenated, the output layer's averaged.

Layer ``l`` has ``K`` heads of width ``d``; ``N(i)`` is the stored
in-neighbours of ``i`` (the stored graph holds every self edge), ``p``
the attention dropout (training only)::

    q_i = W_q x_i + b_q,  k_j = W_k x_j + b_k,  v_j = W_v x_j + b_v,
    r_i = W_r x_i + b_r                   (q, k, v [K*d]; r [K*d], or
                                           [d] where heads are averaged)
    s_ij^h  = q_i^h . k_j^h / sqrt(d)                      j in N(i)
    alpha^h = softmax_j(s_ij^h)
    t_ij^h  = alpha_ij^h D_ij^h / (1 - p),  D ~ Bernoulli(1 - p)
    m_i     = concat_h sum_j t_ij^h v_j^h     (hidden; mean_h: output)
    beta_i  = sigmoid(w_beta . [m_i; r_i; m_i - r_i])
    o_i     = beta_i r_i + (1 - beta_i) m_i
    x^{l+1} = relu(LN(o))              logits = o at the last layer

Built from builder ops: three ``linear(bias=True)`` a layer (``W_q``;
``W_k`` and ``W_v`` as ONE ``[in, 2*K*d]`` matrix whose output is the
``[k | v]`` table the edges gather; ``W_r``), ``transformer_attention``
(the attention, the dropout and the gate), ``layer_norm``, ``relu``.

``layers`` follows the CLI convention ``F-H-...-H-C``: layers[0] is the
input width, layers[-1] the class count, each entry between a hidden
layer's concatenated width ``K * d`` (``heads`` must divide it); the
output layer has ``K`` heads of width ``C``, averaged.  Parameters, in
construction order, layer ``l``: ``linear_<3l>`` (+ ``_b``) ``W_q``,
``linear_<3l+1>`` (+ ``_b``) ``[W_k | W_v]``, ``linear_<3l+2>`` (+
``_b``) ``W_r``, ``tfattn_<l>_beta`` ``w_beta``, and for a hidden layer
``ln_<l>_scale`` / ``_shift``.  At 128-256-256-40 with 2 heads that is
133,376 + 264,448 + 72,080 = 469,904 trainable scalars.

Departures from UniMP: its masked label input (a share of the training
labels fed in as an embedding each step) is not built — the model's
input is the features alone.  Weights are initialized Glorot-uniform
(the repository's rule), biases and ``w_beta`` as torch's.
"""

from __future__ import annotations

from typing import Sequence

from .builder import Model
from ..ops.dense import AC_MODE_NONE


def build_gtrans(layers: Sequence[int], dropout_rate: float = 0.3,
                 heads: int = 2) -> Model:
    """``dropout_rate``: the attention dropout ``p`` (the family has no
    other dropout)."""
    if len(layers) < 2:
        raise ValueError("gtrans needs an input and an output width")
    if heads < 1:
        raise ValueError(f"heads must be >= 1, got {heads}")
    bad = [h for h in layers[1:-1] if h % heads]
    if bad:
        raise ValueError(f"hidden widths {bad} not divisible by "
                         f"{heads} heads")
    model = Model(in_dim=layers[0])
    h = model.input()
    n = len(layers)
    for i in range(1, n):
        last = i == n - 1
        width = heads * layers[i] if last else layers[i]

        def proj(w):
            return model.linear(h, w, AC_MODE_NONE, bias=True)

        q, kv = proj(width), proj(2 * width)
        o = model.transformer_attention(
            q, kv, proj(layers[i]), heads, concat=not last,
            rate=dropout_rate)
        h = o if last else model.relu(model.layer_norm(o))
    model.softmax_cross_entropy(h)
    return model

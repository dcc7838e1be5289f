"""DeeperGCN model family: deep GCNs by pre-activation residual blocks
and generalized neighbour aggregation (Li, Xiong, Thabet, Ghanem,
*DeeperGCN: All You Need to Train Deeper GCNs*, arXiv:2006.07739), as
the authors' repository runs it full batch on ogbn-arxiv
(``github.com/lightaime/deep_gcns_torch``,
``examples/ogb/ogbn_arxiv/``: ``python main.py --use_gpu --self_loop
--num_layers 28 --block res+ --gcn_aggr softmax_sg --t 0.1``, the
"DeeperGCN" row of the OGB ogbn-arxiv leaderboard).

With ``N(v)`` the stored in-neighbours of ``v`` (the stored graph holds
every self edge: the script's ``--self_loop``), ``t`` the temperature,
``eps = 1e-7``, ``sg`` = no gradient flows, and ``*``, ``exp`` and the
division per channel::

    h^0     = X W_enc + b_enc                                  [V, H]
    S(z)_v  = z_v + sum_{u in N(v)} sg(w_vu) * m_u,
              m_u  = relu(z_u) + eps,
              w_vu = exp(t m_u) / sum_{u' in N(v)} exp(t m_u')
    G_l(z)  = S(z) W_l + b_l            (GENConv, aggr softmax_sg,
                                         its MLP one Linear)
    h^1     = G_0(h^0)
    h^{l+1} = h^l + G_l(dropout(relu(BN_{l-1}(h^l))))   l = 1 .. L-1
                                                      (block res+)
    logits  = dropout(relu(BN_{L-1}(h^L))) W_out + b_out
    BN(x)   = gamma * (x - mu) / sqrt(sigma^2 + 1e-5) + beta

In training ``mu``, ``sigma^2`` are the moments over all ``V`` vertex
rows (biased variance) and the op's running mean / variance move by
momentum 0.1 (the running variance from the unbiased estimate);
evaluation reads the running ones.  ``sg(w)`` is the script's
``torch.no_grad()`` around ``scatter_softmax``: the backward of ``S``
is not the derivative of the expression above but ``dL/dm_u = sum_{v:
u in N(v)} w_vu * g_v`` (``GraphContext.soft_aggregate``,
``ops/softagg.py``).

Built from builder ops: ``linear(bias=True)``, ``soft_aggregate``,
``batch_norm``, ``relu``, ``dropout``, ``add``.  The res+ order (norm
-> ReLU -> dropout -> convolution -> add) is the paper's
pre-activation block, beside GCNII's post-activation ``lerp``.

``layers`` follows the CLI convention ``F-H-...-H-C``: layers[0] is
the input feature dim, layers[-1] the class count, and each
intermediate entry one GENConv layer (all must share one width H —
the residual adds ``h^l`` into every layer).  Parameters, in
construction order: ``linear_0`` (+ ``_b``) = the encoder;
``linear_<l+1>`` (+ ``_b``) = ``W_l``, ``b_l``; ``bn_<l>_scale`` /
``_shift`` (and the statistics ``bn_<l>_mean`` / ``_var``) = ``BN_l``;
last ``linear_<L+1>`` (+ ``_b``) = the classifier.  At the published
widths (128-128x28-40) that is 16,512 + 28 x 16,512 + 28 x 256 + 5,160
= 491,176 trainable scalars, the leaderboard row's count.

Departures from the script: none in arithmetic.  ``learn_t`` (a
trainable temperature), message normalization, the other aggregators
(``softmax``, ``power``, ``mean``, ``max``) and MLPs deeper than one
layer are the script's options at values the README's command does not
take, and are not built.  Weights are initialized Glorot-uniform (the
repository's rule; torch's ``Linear`` draws U(+-1/sqrt(in))), biases as
torch's.
"""

from __future__ import annotations

from typing import Sequence

from .builder import Model
from ..ops.dense import AC_MODE_NONE

# the README's command
TEMPERATURE = 0.1
# GenMessagePassing's eps
MESSAGE_EPS = 1e-7


def build_deepergcn(layers: Sequence[int], t: float = TEMPERATURE,
                    dropout_rate: float = 0.5) -> Model:
    if len(layers) < 3:
        raise ValueError(
            "DeeperGCN needs at least one GENConv layer (F-H-C); for a "
            "propagation-free linear model use --model sgc")
    hidden = layers[1]
    if any(h != hidden for h in layers[1:-1]):
        raise ValueError(
            f"DeeperGCN hidden widths must all match (the residual "
            f"adds h^l into every layer), got {layers[1:-1]}")
    if t <= 0.0:
        raise ValueError(f"t must be > 0, got {t}")
    model = Model(in_dim=layers[0])

    def conv(z):
        return model.linear(model.soft_aggregate(z, t, MESSAGE_EPS),
                            hidden, AC_MODE_NONE, bias=True)

    def pre_activation(h):
        return model.dropout(model.relu(model.batch_norm(h)),
                             dropout_rate)

    h = model.linear(model.input(), hidden, AC_MODE_NONE, bias=True)
    h = conv(h)
    for _ in range(len(layers) - 3):
        h = model.add(h, conv(pre_activation(h)))
    out = model.linear(pre_activation(h), layers[-1], AC_MODE_NONE,
                       bias=True)
    model.softmax_cross_entropy(out)
    return model

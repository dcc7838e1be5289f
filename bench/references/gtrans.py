"""Plain forward of the Graph Transformer of UniMP (Shi, Huang, Feng,
Zhong, Wang, Sun, *Masked Label Prediction: Unified Message Passing
Model for Semi-Supervised Classification*, IJCAI 2021,
arXiv:2009.03509) with the layer equations of PyTorch Geometric's
``TransformerConv(in, d, heads=K, concat=..., beta=True,
dropout=p)``, stacked as its ogbn-arxiv rows run it full batch, in
inference mode (the attention dropout is the identity).  One layer, for
input ``x`` [V, d_in], ``K`` heads of width ``d``::

    q = x W_q + b_q,  k = x W_k + b_k,  v = x W_v + b_v      [V, K*d]
    r = x W_r + b_r                       [V, K*d], or [V, d] averaged
    s_ij^h     = q_i^h . k_j^h / sqrt(d)       for every stored edge j -> i
    alpha_ij^h = exp(s_ij^h - mx_i^h) / sum_j' exp(s_ij'^h - mx_i^h)
    m_i        = concat_h sum_j alpha_ij^h v_j^h     (mean_h at the output)
    beta_i     = sigmoid([m_i, r_i, m_i - r_i] . w_beta)
    o_i        = beta_i r_i + (1 - beta_i) m_i

hidden layers ``x <- relu(LN(o))`` with ``LN(z) = gamma (z - mean) /
sqrt(var + 1e-5) + b`` over the row's channels (biased variance); the
output layer's ``o`` is the logits.  The neighbourhood is the stored row
(the graph holds every self edge); a row with no stored edge gets ``m_i
= 0``.  The edge softmax is three passes over the stored edge list in
chunks: a segment max, then ``exp`` and two segment sums (denominator,
numerator).  No buckets, no hashing, no hand-written gradient: nothing
is imported from ``roc_tpu``.

``layers`` is the CLI's ``-layers`` list: input width first, classes
last; a hidden width is ``K * d``, the output layer's heads are
``classes`` wide.  Parameters are the program's, in construction order,
layer ``l``: ``linear_<3l>`` / ``_b`` (``W_q``, ``b_q``),
``linear_<3l+1>`` / ``_b`` (``[W_k | W_v]`` and ``[b_k | b_v]`` side by
side: the first ``K*d`` columns the keys'), ``linear_<3l+2>`` / ``_b``
(``W_r``, ``b_r``), ``tfattn_<l>_beta`` (``w_beta`` [3 * out]) and, for
a hidden layer, ``ln_<l>_scale`` / ``ln_<l>_shift``.

Departures from ``TransformerConv``, each by construction of the
configuration, none in arithmetic: the keys' and values' weights are
one matrix (the same function); no edge features (``edge_dim`` None);
no root-weight switch (``root_weight`` True, as UniMP's).  UniMP's
masked label input is absent from the program and so from here
(``configs/gtrans-arxiv.json``, ``assumed``).  The layer equations are
written from memory of PyG's source: there is no network to check
them against.

``forward``'s keywords exist for the tests and the probe only:
``keep(dst, src, layer)`` returns the ``[E', K]`` multiplier of the
attention dropout on the edges it is handed (the tests hand it the
program's hashed mask, so that ``jax.grad`` of this forward is the
gradient the program's two-pass rule must equal); ``attend`` swaps in
an edge softmax of a lower precision and ``stored`` rounds what a
program under test would store (``probes/gtrans_precision.py``).  The
reference proper is float32 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense

LN_EPS = 1e-5
TRAIN = 1                      # the program's MASK_TRAIN


def _over_edges(step, carry, graph):
    """``carry = step(carry, (src, dst))`` over the whole chunks of the
    stored edge list, then over the tail."""
    if graph.src.shape[0]:
        carry, _ = jax.lax.scan(lambda c, sd: (step(c, sd), None), carry,
                                (graph.src, graph.dst))
    if graph.tail_src.shape[0]:
        carry = step(carry, (graph.tail_src, graph.tail_dst))
    return carry


def edge_attention(q, k, v, graph, heads, keep=None):
    """``m_i^h = sum_j softmax_j(q_i^h . k_j^h / sqrt(d)) v_j^h`` over
    the stored edges, heads side by side ``[V, K*d]``; ``keep(dst,
    src)`` (None: none) multiplies each edge's normalized weight."""
    V = q.shape[0]
    d = q.shape[1] // heads
    qh = q.reshape(V, heads, d)
    kh = k.reshape(V, heads, d)
    vh = v.reshape(V, heads, d)

    def score(src, dst):
        return jnp.einsum("ekd,ekd->ek", qh[dst], kh[src],
                          precision=jax.lax.Precision.HIGHEST) / d ** 0.5

    def row_max(mx, sd):
        src, dst = sd
        return mx.at[dst].max(score(src, dst), indices_are_sorted=True)

    mx = _over_edges(row_max, jnp.full((V, heads), -jnp.inf, q.dtype),
                     graph)
    # the softmax does not depend on the shift, so it carries no gradient
    mx = jax.lax.stop_gradient(jnp.where(jnp.isfinite(mx), mx, 0))

    def sums(carry, sd):
        den, num = carry
        src, dst = sd
        w = jnp.exp(score(src, dst) - mx[dst])               # [c, K]
        den = den.at[dst].add(w, indices_are_sorted=True)
        if keep is not None:
            w = w * keep(dst, src)
        part = w[:, :, None] * vh[src]
        num = num.at[dst].add(part.reshape(src.shape[0], -1),
                              indices_are_sorted=True)
        return den, num

    den, num = _over_edges(
        sums, (jnp.zeros((V, heads), q.dtype), jnp.zeros_like(q)), graph)
    den = jnp.maximum(den, jnp.finfo(den.dtype).tiny)
    return (num.reshape(V, heads, d) / den[:, :, None]).reshape(q.shape)


def layer_norm(z, scale, shift):
    mean = jnp.mean(z, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(z - mean), axis=1, keepdims=True)
    return scale * (z - mean) / jnp.sqrt(var + LN_EPS) + shift


def forward(params, x, graph, model, keep=None, attend=edge_attention,
            stored=lambda a: a):
    layers = [int(d) for d in model["layers"]]
    heads = int(model["heads"])
    p = params
    n = len(layers)
    h = stored(x)

    def lin(h, k):
        return dense(h, stored(p[f"linear_{k}"])) + stored(
            p[f"linear_{k}_b"])

    for l in range(n - 1):
        last = l == n - 2
        q = stored(lin(h, 3 * l))
        kv = stored(lin(h, 3 * l + 1))
        r = stored(lin(h, 3 * l + 2))
        F = q.shape[1]
        m = attend(q, kv[:, :F], kv[:, F:], graph, heads,
                   None if keep is None
                   else (lambda dst, src, l=l: keep(dst, src, l)))
        if last:
            m = m.reshape(m.shape[0], heads, -1).mean(axis=1)
        w = stored(p[f"tfattn_{l}_beta"]).reshape(3, -1)
        beta = jax.nn.sigmoid(jnp.sum(m * w[0] + r * w[1]
                                      + (m - r) * w[2], axis=1))[:, None]
        o = stored(beta * r + (1.0 - beta) * m)
        if last:
            return o
        h = stored(jax.nn.relu(stored(layer_norm(
            o, p[f"ln_{l}_scale"], p[f"ln_{l}_shift"]))))


def loss_and_grads(params, x, labels, mask, graph, model, keep=None):
    """``(loss, d loss / d params)`` of the training objective on the
    reference's own logits, with the attention dropout ``keep`` (see
    :func:`forward`): the cross-entropy summed over the train rows."""

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, x, graph, model, keep=keep),
                                  axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(mask == TRAIN, ll, 0.0))

    return jax.value_and_grad(loss)(params)

"""Plain forward of the graph attention network (Velickovic et al.
2018) as the OGB ogbn-arxiv leaderboard's full-batch GAT runs it (DGL
``examples/pytorch/ogb/ogbn-arxiv/gat.py``), in inference mode (both
dropouts are the identity).  One layer, for input ``h`` [V, d_in], ``K``
heads of width ``d``::

    z = h W                           W [d_in, K*d];  z_i^k in R^d
    s_j^k = a_src^k . z_j^k           t_i^k = a_dst^k . z_i^k
    e_ij^k = LeakyReLU_0.2(s_j^k + t_i^k)       for every stored edge j -> i
    alpha_ij^k = exp(e_ij^k - m_i^k) / sum_j' exp(e_ij'^k - m_i^k)
    o_i^k = sum_j alpha_ij^k z_j^k              m_i^k = max_j e_ij^k
    h'_i = concat_k o_i^k + h_i R               R [d_in, K*d], no bias,
                                                iff model["skip"]

hidden layers ``h <- act(h')`` with ``K = model["heads"]``; the output
layer has one head and gives the logits.  The neighbourhood is the
stored row (the graph holds every self edge); a row with no stored edge
gets ``o_i = 0``.  The edge softmax is three passes over the stored edge
list in chunks: a segment max, then ``exp`` and two segment sums
(denominator, numerator).

``layers`` is the CLI's ``-layers`` list: input width first, classes
last; a hidden width is ``K * d``.  Parameters are the program's, in
construction order: ``linear_<k>`` (``W`` then, with the skip, ``R``,
layer by layer) and ``gat_<l>_src`` / ``gat_<l>_dst`` [K, d].  The
script's BatchNorm, edge drop and output bias are absent from the
program and so from here (``configs/gat-arxiv.json``, ``assumed``).

``forward`` is differentiable; :func:`loss_and_grads` is its training
twin for the tests (the program's objective: cross-entropy summed over
the train rows).  ``forward``'s two keywords exist for
``probes/attention_precision.py`` only, which swaps in an edge softmax
of a lower precision (``attend``) and rounds what a program under test
would store (``stored``): the reference proper is float32 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense

NEG_SLOPE = 0.2
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


def edge_softmax_sum(z, s, t, graph):
    """``o_i^k = sum_j softmax_j(LeakyReLU(s_j^k + t_i^k)) z_j^k`` over
    the stored edges; ``z`` [V, K*d], ``s`` / ``t`` [V, K]."""
    V, K = s.shape

    def score(sd):
        src, dst = sd
        return jax.nn.leaky_relu(s[src] + t[dst], NEG_SLOPE)

    def row_max(m, sd):
        return m.at[sd[1]].max(score(sd), indices_are_sorted=True)

    m = _over_edges(row_max, jnp.full((V, K), -jnp.inf, s.dtype), graph)
    # the softmax does not depend on the shift, so it carries no gradient
    m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0))

    def sums(carry, sd):
        den, num = carry
        src, dst = sd
        w = jnp.exp(score(sd) - m[dst])                      # [c, K]
        den = den.at[dst].add(w, indices_are_sorted=True)
        part = w[:, :, None] * z[src].reshape(src.shape[0], K, -1)
        num = num.at[dst].add(part.reshape(src.shape[0], -1),
                              indices_are_sorted=True)
        return den, num

    den, num = _over_edges(
        sums, (jnp.zeros((V, K), s.dtype), jnp.zeros_like(z)), graph)
    den = jnp.maximum(den, jnp.finfo(den.dtype).tiny)
    return (num.reshape(V, K, -1) / den[:, :, None]).reshape(z.shape)


def forward(params, x, graph, model, attend=edge_softmax_sum,
            stored=lambda a: a):
    layers = [int(d) for d in model["layers"]]
    n = len(layers)
    skip = bool(model.get("skip"))
    act = {"relu": jax.nn.relu, "elu": jax.nn.elu}[
        model.get("activation", "elu")]
    h, k = stored(x), 0
    for i in range(1, n):
        heads = 1 if i == n - 1 else int(model.get("heads", 1))
        z = stored(dense(h, stored(params[f"linear_{k}"])))
        k += 1
        zk = z.reshape(z.shape[0], heads, -1)
        s = jnp.einsum("vkd,kd->vk", zk,
                       stored(params[f"gat_{i - 1}_src"]),
                       precision=jax.lax.Precision.HIGHEST)
        t = jnp.einsum("vkd,kd->vk", zk,
                       stored(params[f"gat_{i - 1}_dst"]),
                       precision=jax.lax.Precision.HIGHEST)
        out = stored(attend(z, s, t, graph))
        if skip:
            out = stored(out + stored(
                dense(h, stored(params[f"linear_{k}"]))))
            k += 1
        h = out if i == n - 1 else stored(act(out))
    return h


def loss_and_grads(params, x, labels, mask, graph, model):
    """``(loss, d loss / d params)`` of the training objective on the
    reference's own logits: the cross-entropy summed over the train
    rows."""

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, x, graph, model), axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(mask == TRAIN, ll, 0.0))

    return jax.value_and_grad(loss)(params)

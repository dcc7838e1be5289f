"""Plain forward of DeeperGCN (Li, Xiong, Thabet, Ghanem, *DeeperGCN:
All You Need to Train Deeper GCNs*, arXiv:2006.07739) as the authors'
repository runs it full batch on ogbn-arxiv
(``github.com/lightaime/deep_gcns_torch``, ``examples/ogb/ogbn_arxiv/``:
``--self_loop --num_layers 28 --block res+ --gcn_aggr softmax_sg --t
0.1`` at the script's other defaults), in inference mode: dropout is
the identity and every BatchNorm reads the running statistics it is
handed.  With ``N(v)`` the stored in-neighbours of ``v`` (the stored
graph holds every self edge), ``t = model["t"]``, ``eps = 1e-7``::

    h^0     = X W_enc + b_enc
    S(z)_v  = z_v + sum_{u in N(v)} w_vu * m_u,   m_u = relu(z_u) + eps,
              w_vu = exp(t m_u) / sum_{u' in N(v)} exp(t m_u')
    G_l(z)  = S(z) W_l + b_l
    h^1     = G_0(h^0)
    h^{l+1} = h^l + G_l(relu(BN_{l-1}(h^l)))              l = 1 .. L-1
    logits  = relu(BN_{L-1}(h^L)) W_out + b_out
    BN(x)   = gamma * (x - mean) / sqrt(var + 1e-5) + beta

The softmax is computed as published — per destination: the maximum of
``t m`` over its stored in-edges (a ``segment_max`` of this file), the
exponentials of the differences, their ``segment_sum`` and the weighted
``segment_sum`` over the stored edge list in chunks
(``reference.aggregate_sum``'s scan, with the per-edge weight inside).
Nothing is imported from ``roc_tpu``; the program's one-table form
(a per-channel shift, ``[e * m, e]`` summed once) is its own.

``layers`` is the CLI's ``-layers`` list: input width first, classes
last, one entry a GENConv layer between.  Parameters are the program's,
in construction order: ``linear_0`` / ``linear_0_b`` the encoder,
``linear_<l+1>`` / ``_b`` the ``W_l``, ``b_l``, ``bn_<l>_scale`` /
``_shift`` / ``_mean`` / ``_var`` the ``BN_l`` with its running
statistics, ``linear_<L+1>`` / ``_b`` the classifier.

Departures from the script: none in arithmetic.  Its ``log_softmax`` +
NLL over the train rows is the program's summed cross-entropy.

:func:`loss_and_grads` is the training twin for the tests: batch
statistics over all ``V`` rows (two-pass: the mean, then the mean of
squared deviations; biased variance), the dropout masks it is handed
(``masks[k]`` multiplies the k-th dropout's input: the keep mask over
the keep probability), ``jax.lax.stop_gradient`` on the softmax weights
(``detach=False`` differentiates through them: what the published rule
is NOT), the program's objective, and the running statistics a step
leaves (momentum 0.1, unbiased variance).  ``forward``'s keywords
beyond ``detach`` exist for ``probes/deepergcn_precision.py`` only:
``stored`` rounds what a program under test would store, ``sums``
swaps in neighbour sums of a lower precision, ``norm`` a BatchNorm of a
lower precision.  The reference proper is float32 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense

TRAIN = 1                      # the program's MASK_TRAIN
EPS_MSG = 1e-7
EPS_BN = 1e-5
MOMENTUM = 0.1


def scan_edges(step, acc, graph):
    """``acc`` after ``step(acc, (src, dst)) -> (acc, None)`` over the
    stored edge list: a scan over its whole chunks, then its tail
    (``reference.aggregate_sum``'s walk, with the step handed in)."""
    if graph.src.shape[0]:
        acc, _ = jax.lax.scan(step, acc, (graph.src, graph.dst))
    if graph.tail_src.shape[0]:
        acc, _ = step(acc, (graph.tail_src, graph.tail_dst))
    return acc


def segment_max(x, graph):
    """``out[v] = max over stored edges (u -> v) of x[u]`` (``-inf``
    for a vertex without one)."""

    def step(acc, sd):
        s, d = sd
        return acc.at[d].max(x[s], indices_are_sorted=True), None

    return scan_edges(step, jnp.full((graph.num_nodes, x.shape[1]),
                                     -jnp.inf, x.dtype), graph)


def soft_sums(m, logit, graph):
    """``(sum_u e_vu m_u, sum_u e_vu)`` with ``e_vu = exp(logit_u -
    max_{u'} logit_u')`` per destination ``v`` and channel, over the
    stored edges in chunks."""
    top = segment_max(logit, graph)

    def step(acc, sd):
        s, d = sd
        e = jnp.exp(logit[s] - top[d])
        return (acc[0].at[d].add(e * m[s], indices_are_sorted=True),
                acc[1].at[d].add(e, indices_are_sorted=True)), None

    zero = jnp.zeros((graph.num_nodes, m.shape[1]), m.dtype)
    return scan_edges(step, (zero, zero), graph)


def soft_aggregate(z, graph, t, detach=True, sums=soft_sums):
    """``S(z)``.  ``detach`` puts ``jax.lax.stop_gradient`` on the
    weights: they are a function of ``t m`` alone, so with that
    argument constant the numerator is linear in the live ``m`` and the
    denominator a constant — the derivative is ``w_vu``, the published
    rule."""
    m = jax.nn.relu(z) + EPS_MSG
    logit = t * m
    if detach:
        logit = jax.lax.stop_gradient(logit)
    num, den = sums(m, logit, graph)
    return z + jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def batch_norm(x, p, name):
    """Inference: the running statistics."""
    return (p[f"{name}_scale"] * (x - p[f"{name}_mean"])
            / jnp.sqrt(p[f"{name}_var"] + EPS_BN) + p[f"{name}_shift"])


def forward(params, x, graph, model, stored=lambda a: a,
            sums=soft_sums, norm=batch_norm):
    n_conv = len(model["layers"]) - 2
    t = float(model["t"])
    p = params

    def lin(h, k):
        return (dense(h, stored(p[f"linear_{k}"]))
                + stored(p[f"linear_{k}_b"]))

    def conv(z, k):
        return stored(lin(stored(soft_aggregate(
            z, graph, t, detach=False, sums=sums)), k))

    h = stored(lin(stored(x), 0))
    h = conv(h, 1)
    for l in range(1, n_conv):
        z = stored(jax.nn.relu(norm(h, p, f"bn_{l - 1}")))
        h = stored(h + conv(z, l + 1))
    z = stored(jax.nn.relu(norm(h, p, f"bn_{n_conv - 1}")))
    return lin(z, n_conv + 1)


def loss_and_grads(params, x, labels, mask, graph, model, masks,
                   detach=True):
    """``(loss, d loss / d params, statistics)`` of one training step on
    the reference's own arithmetic: batch statistics, the given dropout
    ``masks`` (one ``[V, H]`` multiplier a dropout, in order), the
    detached softmax weights, the cross-entropy summed over the train
    rows; ``statistics`` maps every ``bn_<l>_mean`` / ``_var`` to its
    value after the step.  The gradient is with respect to every entry
    of ``params`` that is a parameter (the statistics' is zero)."""
    n_conv = len(model["layers"]) - 2
    t = float(model["t"])
    n_rows = x.shape[0]

    def run(p):
        moved = {}

        def lin(h, k):
            return dense(h, p[f"linear_{k}"]) + p[f"linear_{k}_b"]

        def bn(h, l):
            name = f"bn_{l}"
            mean = jnp.mean(h, axis=0)
            var = jnp.mean((h - mean) ** 2, axis=0)
            const = jax.lax.stop_gradient
            moved[f"{name}_mean"] = ((1 - MOMENTUM) * p[f"{name}_mean"]
                                     + MOMENTUM * const(mean))
            moved[f"{name}_var"] = (
                (1 - MOMENTUM) * p[f"{name}_var"]
                + MOMENTUM * const(var) * n_rows / (n_rows - 1))
            return (p[f"{name}_scale"] * (h - mean)
                    / jnp.sqrt(var + EPS_BN) + p[f"{name}_shift"])

        def conv(z, k):
            return lin(soft_aggregate(z, graph, t, detach=detach), k)

        h = conv(lin(x, 0), 1)
        for l in range(1, n_conv):
            h = h + conv(jax.nn.relu(bn(h, l - 1)) * masks[l - 1], l + 1)
        z = jax.nn.relu(bn(h, n_conv - 1)) * masks[n_conv - 1]
        logp = jax.nn.log_softmax(lin(z, n_conv + 1), axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(mask == TRAIN, ll, 0.0)), moved

    (loss, moved), grads = jax.value_and_grad(run, has_aux=True)(params)
    return loss, grads, moved

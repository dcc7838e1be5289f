"""Plain forward of R-GCN (Schlichtkrull et al., ESWC 2018,
arXiv:1703.06103) as OGB ships it for ogbn-mag
(``github.com/snap-stanford/ogb``, ``examples/nodeproppred/mag/rgcn.py``,
its ``RGCNConv``), in inference mode (dropout is the identity).

The graph is ONE stored CSR (``reference.Graph``); ``model["node_types"]``
gives the vertex kinds as counts of contiguous id ranges, in id order.
An edge's relation is the ordered pair of its endpoints' kinds, a
stored self edge belongs to none.  One layer, for a vertex ``v`` of
kind ``k(v)`` and every relation ``r = (s -> d)``::

    h'_v = h_v W_root[k(v)] + b[k(v)]
           + sum_r (1 / |N_r(v)|) sum_{u in N_r(v)} h_u W_r

with ``N_r(v)`` the sources of kind ``s`` among ``v``'s stored
non-self in-edges (a relation with no in-edge at ``v`` adds 0), ReLU
between layers and none after the last; and the input::

    h^0_v = x_v                 a kind that is not in ``model["embed_types"]``
          = embed_<k>[v - lo_k]  a kind that is: a trainable row

Computed literally: for each relation the product ``h W_r`` over the
source kind's rows, a masked ``reference.aggregate_sum`` of it over the
stored edge list (every edge of another relation, and every self edge,
reads a zero row) and the division by that relation's own in-degree;
for each kind the root product and bias.  The sum runs over the
destination kind's rows only, so the widest layer's float32 arrays are
a kind tall, not ``V``.

Which relations exist is read here from the kinds and the edge list,
never taken from the program: every ordered pair of kinds is a
candidate, a pair holds a weight ``rel<l>_<s>_<d>`` or none, and an
edge whose pair holds none turns every logit into NaN — a program that
derived fewer relations than the file stores is not ``correct``.  The
parameter names are the only thing shared with ``roc_tpu``:
``embed_<k>``, ``rel<l>_<s>_<d>`` ``[in, out]``, ``root<l>_<k>`` ``[in,
out]`` and ``root<l>_<k>_b`` ``[out]``.

``forward``'s two keywords exist for ``probes/rgcn_precision.py`` only
(``mean``: a relation mean of a lower precision; ``stored``: the
rounding of what a program under test would store): the reference
proper is float32 throughout.  :func:`loss_and_grads` is the training
twin for the tests (cross-entropy summed over the train rows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import Graph, aggregate_sum, dense

TRAIN = 1                      # the program's MASK_TRAIN


def kind_ranges(model):
    off = np.concatenate([[0], np.cumsum(model["node_types"])])
    return [(int(lo), int(hi)) for lo, hi in zip(off[:-1], off[1:])]


def relation_graph(graph, lo_s, hi_s, lo_d, hi_d):
    """``(sub, degree)``: the stored edges of the relation ``[lo_s,
    hi_s) -> [lo_d, hi_d)`` as a ``reference.Graph`` over the two kinds'
    own row numbers — every other edge, and every self edge, re-pointed
    at the zero row appended to the source table (and clamped onto a
    destination row, where adding zeros changes nothing) — and the
    relation's in-degree of each destination row."""
    n_s, n_d = hi_s - lo_s, hi_d - lo_d

    def remap(src, dst):
        inside = ((src >= lo_s) & (src < hi_s) & (dst >= lo_d)
                  & (dst < hi_d) & (src != dst))
        return (jnp.where(inside, src - lo_s, n_s).astype(jnp.int32),
                jnp.clip(dst - lo_d, 0, n_d - 1).astype(jnp.int32),
                inside)

    s, d, m = remap(graph.src, graph.dst)
    ts, td, tm = remap(graph.tail_src, graph.tail_dst)
    degree = (jnp.zeros(n_d, jnp.float32)
              .at[d.reshape(-1)].add(m.reshape(-1).astype(jnp.float32))
              .at[td].add(tm.astype(jnp.float32)))
    return Graph(s, d, ts, td, degree, n_d), degree


def relation_mean(y, sub, degree):
    """The mean over a relation's in-neighbours of ``y`` (the source
    kind's rows, a zero row appended); 0 where it has none."""
    total = aggregate_sum(y, sub)
    return jnp.where(degree[:, None] > 0,
                     total / jnp.maximum(degree, 1.0)[:, None], 0.0)


def forward(params, x, graph, model, mean=relation_mean,
            stored=lambda a: a):
    layers = [int(d) for d in model["layers"]]
    ranges = kind_ranges(model)
    embed = set(int(k) for k in model["embed_types"])
    h = stored(jnp.concatenate([
        params[f"embed_{k}"] if k in embed else x[lo:hi]
        for k, (lo, hi) in enumerate(ranges)], axis=0))
    last = len(layers) - 2
    kinds = range(len(ranges))
    # every ordered pair of kinds, once: its stored edges as a graph of
    # its own and its in-degrees
    pairs = {(s, d): relation_graph(graph, *ranges[s], *ranges[d])
             for s in kinds for d in kinds}
    # edges of a pair of kinds that holds no weight: not a relation
    # the program derived
    orphan = sum((deg.sum() for (s, d), (_, deg) in pairs.items()
                  if f"rel0_{s}_{d}" not in params),
                 jnp.zeros((), jnp.float32))
    for l in range(len(layers) - 1):
        blocks = []
        for d, (lo_d, hi_d) in enumerate(ranges):
            out = (dense(h[lo_d:hi_d], stored(params[f"root{l}_{d}"]))
                   + params[f"root{l}_{d}_b"])
            for s, (lo_s, hi_s) in enumerate(ranges):
                w = params.get(f"rel{l}_{s}_{d}")
                if w is None:
                    continue
                y = stored(dense(h[lo_s:hi_s], stored(w)))
                y = jnp.concatenate(
                    [y, jnp.zeros((1, y.shape[1]), y.dtype)])
                out = out + stored(mean(y, *pairs[s, d]))
            blocks.append(out)
        h = jnp.concatenate(blocks, axis=0)
        if l != last:
            h = stored(jax.nn.relu(h))
    return jnp.where(orphan > 0, jnp.nan, h)


def loss_and_grads(params, x, labels, mask, graph, model):
    """``(loss, d loss / d params)`` of the training objective on the
    reference's own logits: the cross-entropy summed over the train
    rows."""

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, x, graph, model), axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(mask == TRAIN, ll, 0.0))

    return jax.value_and_grad(loss)(params)

"""R-GCN over a typed graph (Schlichtkrull, Kipf, Bloem, van den Berg,
Titov, Welling, *Modeling Relational Data with Graph Convolutional
Networks*, ESWC 2018, arXiv:1703.06103), in the form OGB ships as the
full-batch baseline of ogbn-mag
(``github.com/snap-stanford/ogb``, ``examples/nodeproppred/mag/rgcn.py``,
its ``RGCNConv``: no basis decomposition, a mean a relation).

Vertex kinds are contiguous id ranges and an edge's relation is the
ordered pair of its endpoints' kinds (``core/relations.py``).  One
layer, for a vertex ``v`` of kind ``k(v)`` and relations ``r = (s ->
d)``::

    h'_v = W_root[k(v)] h_v + b[k(v)]
           + sum_r (1 / |N_r(v)|) sum_{u in N_r(v)} W_r h_u

(a relation with no in-edge at ``v`` adds 0; the file's self edges are
not relation edges: the root term is the self connection), and::

    h^0_v = x_v              the file's feature row, for a kind that has them
          = E_{k(v)}[v]      a trainable row, for a kind of ``embed_types``

ReLU and dropout between layers, none after the last; the loss reads
the train rows, which the mask puts on kind 0 alone.  Parameters, in
construction order: ``embed_<k>`` ``[n_k, F]`` a trainable kind; then
a layer ``l``: ``rel<l>_<s>_<d>`` ``[in, out]`` a relation (bias-free)
and ``root<l>_<k>`` ``[in, out]``, ``root<l>_<k>_b`` ``[out]`` a kind.

By linearity ``sum_r mean_r(h W_r)`` is ONE weighted sum over the
union edge list (``GraphContext.rel_aggregate``), and the product can
sit on either side of it: the trainer's resolve pass picks the side a
layer (``core/relations.py resolve_rel_order``) — at ``64 -> 349``
gathering the 64-wide means first reads a third of the bytes of
gathering 349-wide products.

Which program runs which relations: the eval / predict program
(``Model.apply(train=False)``) runs every layer over all relations and
returns a logit row for every vertex of every kind.  The loss program
(``Model.loss_fn`` in train mode, ``models/builder.py
Model.loss_cut``) reads kind 0's rows alone, so its LAST layer sums
only the relations that end in kind 0, over tables of that subset
(``core/relations.py TypedGraph.restrict``), multiplies only their
blocks and kind 0's root weight, and hands the loss an array a kind
tall; every earlier layer runs whole (its other kinds feed the last
layer's sources).  Loss and gradients are the whole layer's: what the
cut leaves out no output of that program depends on, and the
parameters it leaves unused get the zero gradient they had.

``layers`` follows the CLI convention ``F-H-...-C``.
"""

from __future__ import annotations

from typing import Sequence, Tuple

from .builder import Model


def build_rgcn(layers: Sequence[int], dropout_rate: float = 0.5,
               node_types: Sequence[int] = (),
               embed_types: Sequence[int] = (),
               relations: Sequence[Tuple[int, int]] = ()) -> Model:
    if len(layers) < 2:
        raise ValueError("R-GCN needs at least one layer (F-C)")
    if not node_types:
        raise ValueError("R-GCN needs the typed graph's kind counts "
                         "(node_types)")
    bad = [k for k in embed_types if not 0 < k < len(node_types)]
    if bad:
        raise ValueError(
            f"embed_types {bad} name no kind of {len(node_types)} "
            f"(kind 0 carries the file's features and the labels)")
    model = Model(in_dim=layers[0])
    t = model.typed_input(model.input(), node_types, embed_types,
                          relations)
    last = len(layers) - 2
    for l, out_dim in enumerate(layers[1:]):
        t = model.add(model.rel_conv(t, out_dim, l),
                      model.root_linear(t, out_dim, l))
        if l != last:
            t = model.relu(t)
            t = model.dropout(t, dropout_rate)
    model.softmax_cross_entropy(t)
    return model

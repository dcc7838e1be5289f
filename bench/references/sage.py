"""Plain forward of GraphSAGE with the mean aggregator (Hamilton et al.
2017; OGB's ``gnn.py --use_sage``), in inference mode::

    for each layer i:   t = t W_self,i + mean_{u in N(v)} t_u W_neigh,i
                        t = relu(t)                      but the last

The neighbourhood is the stored row, self edge included, as the program
aggregates it.  Parameters are the program's ``linear_<k>`` in
construction order: self then neighbour, layer by layer.  OGB's example
puts BatchNorm between layers; this repository's model has none, and so
has this reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import aggregate_sum, dense


def forward(params, x, graph, model):
    layers = [int(d) for d in model["layers"]]
    n = len(layers)
    deg = jnp.maximum(graph.degree, 1.0)
    t = x
    for i in range(1, n):
        own = dense(t, params[f"linear_{2 * (i - 1)}"])
        mean = aggregate_sum(t, graph) / deg[:, None]
        t = own + dense(mean, params[f"linear_{2 * (i - 1) + 1}"])
        if i != n - 1:
            t = jax.nn.relu(t)
    return t

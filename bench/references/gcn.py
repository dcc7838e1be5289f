"""Plain forward of the reference driver's GCN stack (``gnn.cc:75-92``,
Kipf & Welling's layer with the reference's residual for deep stacks),
in inference mode (dropout is the identity)::

    for each layer i:   t = D^-1/2 A D^-1/2 (t W_i)      A holds self edges
                        t = relu(t)                      but the last
                        t = t + in_i W'_i                iff len(layers) > 3

``layers`` is the CLI's ``-layers`` list: input width first, classes
last.  Parameters are the program's ``linear_<k>`` in construction
order: one a layer, or main then residual when the stack has residuals.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import aggregate_sum, dense


def forward(params, x, graph, model):
    layers = [int(d) for d in model["layers"]]
    n = len(layers)
    residual = n > 3
    d = jnp.where(graph.degree > 0,
                  1.0 / jnp.sqrt(jnp.maximum(graph.degree, 1.0)), 0.0)
    t, k = x, 0
    for i in range(1, n):
        res = t
        t = dense(t, params[f"linear_{k}"])
        k += 1
        t = aggregate_sum(t * d[:, None], graph) * d[:, None]
        if i != n - 1:
            t = jax.nn.relu(t)
        if residual:
            t = t + dense(res, params[f"linear_{k}"])
            k += 1
    return t

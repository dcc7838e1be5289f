"""In-degree normalization (the reference's InDegreeNorm / GraphNorm op).

Reference (``graphnorm_kernel.cu:45-55``): ``out[v,:] = in[v,:] /
sqrt(indegree(v))`` with the in-degree read off CSR row pointers; applied
both before and after aggregation it yields the symmetric GCN
normalization D^-1/2 A D^-1/2 (self edges pre-added).  The op is its own
linear transpose, which is why the reference backward reuses the forward
kernel (``graphnorm_kernel.cu:127-136``) — JAX autodiff gives the same.

On TPU this is a broadcast multiply by a precomputed ``deg^-1/2`` vector:
degrees are static for a fixed graph, so we fold the rsqrt at trace time
and let XLA fuse the multiply into neighboring ops — cheaper than the
reference's per-element kernel and numerically identical (same
``1/sqrt(deg)`` scalar per row).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def inv_sqrt_degree(in_degree: jax.Array) -> jax.Array:
    """deg^-1/2 with zero-degree rows mapped to 0 (padding rows have
    degree 0; the reference never sees deg 0 thanks to self edges)."""
    deg = in_degree.astype(jnp.float32)
    return jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1.0)), 0.0)


def inv_sqrt_degree_np(in_degree: np.ndarray) -> np.ndarray:
    """Host-side :func:`inv_sqrt_degree` (fp32) — the d vector the
    fused-aggregation weight-table builders bake into the tables
    (core/ell.py ell_weight_tables / SectionedEll.weight_tables,
    parallel/ring.py ring_weight_tables).  Must stay numerically
    identical to the traced form: same max(deg, 1) clamp, same
    zero-degree mapping."""
    deg = np.asarray(in_degree, dtype=np.float32)
    return np.where(deg > 0,
                    1.0 / np.sqrt(np.maximum(deg, 1.0)),
                    0.0).astype(np.float32)


def indegree_norm(x: jax.Array, in_degree: jax.Array) -> jax.Array:
    """x: [V, F]; in_degree: int32 [V].  Returns x / sqrt(indegree).
    Plain XLA: the multiply fuses into neighboring ops."""
    return x * inv_sqrt_degree(in_degree)[:, None].astype(x.dtype)

"""In-degree normalization (the reference's InDegreeNorm / GraphNorm op)
and batch normalization over the vertex axis.

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

Batch normalization (:func:`batch_norm_train`, :func:`batch_norm_eval`)
is beyond the reference: the one op here that reduces over the vertex
axis, and the one with state that is not a parameter (the running
statistics).  ``torch.nn.BatchNorm1d``'s arithmetic: biased variance
for the normalization, unbiased for the running estimate, ``eps`` inside
the square root.  Layer normalization (:func:`layer_norm`) normalizes
each row over its channels instead: no state, no vertex reduction.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import ALLREDUCE_SCOPE, BN_STATS_SCOPE, LN_STATS_SCOPE

BN_EPS = 1e-5
BN_MOMENTUM = 0.1
LN_EPS = 1e-5


def inv_sqrt_degree(in_degree: jax.Array) -> jax.Array:
    """deg^-1/2 with zero-degree rows mapped to 0 (padding rows have
    degree 0; the reference never sees deg 0 thanks to self edges)."""
    deg = in_degree.astype(jnp.float32)
    return jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1.0)), 0.0)


def inv_sqrt_degree_np(in_degree: np.ndarray) -> np.ndarray:
    """Host-side :func:`inv_sqrt_degree` (fp32) — the d vector the
    fused-aggregation weight-table builders bake into the tables
    (core/ell.py ell_weight_tables / SectionedEll.weight_tables,
    parallel/ring.py ring_part_weights).  Must stay numerically
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


def batch_norm_eval(x: jax.Array, scale: jax.Array, shift: jax.Array,
                    mean: jax.Array, var: jax.Array,
                    eps: float = BN_EPS) -> jax.Array:
    """Inference mode: ``scale * (x - mean) / sqrt(var + eps) + shift``
    on the running statistics it is handed, in float32, rounded once to
    ``x``'s dtype."""
    r = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    y = ((x.astype(jnp.float32) - mean.astype(jnp.float32))
         * (r * scale.astype(jnp.float32)) + shift.astype(jnp.float32))
    return y.astype(x.dtype)


def batch_norm_train(x: jax.Array, scale: jax.Array, shift: jax.Array,
                     count: int, valid: Optional[jax.Array] = None,
                     psum: Optional[Callable] = None,
                     eps: float = BN_EPS):
    """Training mode over the rows of ``x`` ``[rows, F]``: ``(y, mean,
    var)`` with ``mean`` and the biased ``var`` the moments over the
    ``count`` real rows — of every partition: ``psum`` (None on one
    device) sums a ``[2, F]`` array across them, ``valid`` ``[rows]``
    (None: all) keeps a partition's padding rows out.

    How the moments are taken: ONE pass, the float32 sums of ``x`` and
    ``x * x`` whatever ``x``'s dtype (``var = E[x^2] - E[x]^2``, floored
    at 0), so the forward reads ``x`` twice (sums, then normalize) and
    one ``[2, F]`` collective serves both moments; the two-pass form
    (``E[(x - mean)^2]``) reads it three times and needs two
    collectives.  The one-pass form loses ``log2(1 + mean^2 / var)``
    bits of the variance to cancellation: under 10 of float32's 24 for
    ``|mean| < 30 sigma``, where pre-activation residual streams stay
    (the tests hold it to the reference's two-pass form at 1e-4).

    The backward is written by hand and keeps ``x`` (in its own dtype)
    and two ``[F]`` vectors; autodiff would keep the float32 ``xhat``.
    With ``xhat = (x - mean) r``, ``r = 1 / sqrt(var + eps)``:
    ``d shift = sum g``, ``d scale = sum g xhat``, ``dx = scale r (g -
    d shift / count - xhat d scale / count)`` — the two sums again ONE
    float32 pass and one ``[2, F]`` ``psum``.  The parameter gradients
    returned are the partition's own sums (the trainers' gradient
    all-reduce adds them up, as every other parameter's); ``mean`` and
    ``var`` carry no gradient (they feed the running statistics)."""
    n = float(count)

    def masked(a):
        a = a.astype(jnp.float32)
        return a if valid is None else jnp.where(valid[:, None], a, 0.0)

    def two_sums(a, b):
        """``psum([sum a, sum a * b])`` over the rows, float32."""
        with jax.named_scope(BN_STATS_SCOPE):
            s = jnp.stack([a.sum(axis=0), (a * b).sum(axis=0)])
            if psum is None:
                return s, s
            with jax.named_scope(ALLREDUCE_SCOPE):
                return s, psum(s)

    def moments(x):
        xf = masked(x)
        _, s = two_sums(xf, xf)
        mean = s[0] / n
        var = jnp.maximum(s[1] / n - mean * mean, 0.0)
        return xf, mean, var

    def normalized(x, scale, shift):
        xf, mean, var = moments(x)
        r = jax.lax.rsqrt(var + eps)
        y = ((xf - mean) * (r * scale.astype(jnp.float32))
             + shift.astype(jnp.float32))
        return y.astype(x.dtype), mean, var, r

    @jax.custom_vjp
    def bn(x, scale, shift):
        return normalized(x, scale, shift)[:3]

    def fwd(x, scale, shift):
        y, mean, var, r = normalized(x, scale, shift)
        return (y, mean, var), (x, scale, mean, r)

    def bwd(res, cts):
        x, scale, mean, r = res
        g = masked(cts[0])
        xhat = (masked(x) - mean) * r
        own, total = two_sums(g, xhat)
        dx = (scale.astype(jnp.float32) * r) * (
            g - total[0] / n - xhat * (total[1] / n))
        if valid is not None:
            dx = jnp.where(valid[:, None], dx, 0.0)
        return (dx.astype(x.dtype), own[1].astype(scale.dtype),
                own[0].astype(scale.dtype))

    bn.defvjp(fwd, bwd)
    return bn(x, scale, shift)


def layer_norm(x: jax.Array, scale: jax.Array, shift: jax.Array,
               eps: float = LN_EPS) -> jax.Array:
    """``torch.nn.LayerNorm`` over the channels of each row of ``x``
    ``[rows, F]``: ``scale * (x - mean) / sqrt(var + eps) + shift``,
    ``mean`` and the biased ``var`` the row's own — the same in train
    and eval, no state, no collective (a row is never split across
    partitions).  The moments are float32 whatever ``x``'s dtype, two
    passes over the row (under ``roc.ln.stats``); the result is rounded
    once to ``x``'s dtype.

    The backward is written by hand and keeps ``x`` and two float32
    scalars a row (mean, ``1 / sqrt(var + eps)``); autodiff would keep
    the float32 ``xhat``.  With ``xhat = (x - mean) r`` and ``h = scale
    * g``: ``dx = r (h - mean_F(h) - xhat mean_F(h xhat))``, ``d scale
    = sum_rows g xhat``, ``d shift = sum_rows g``."""
    f32 = jnp.float32
    n = x.shape[1]

    def stats(x):
        with jax.named_scope(LN_STATS_SCOPE):
            xf = x.astype(f32)
            mean = xf.sum(axis=1, keepdims=True) / n
            var = jnp.square(xf - mean).sum(axis=1, keepdims=True) / n
            return xf, mean, jax.lax.rsqrt(var + eps)

    def normalized(x, scale, shift):
        xf, mean, r = stats(x)
        y = (xf - mean) * r * scale.astype(f32) + shift.astype(f32)
        return y.astype(x.dtype), mean, r

    @jax.custom_vjp
    def ln(x, scale, shift):
        return normalized(x, scale, shift)[0]

    def fwd(x, scale, shift):
        y, mean, r = normalized(x, scale, shift)
        return y, (x, scale, mean, r)

    def bwd(res, g):
        x, scale, mean, r = res
        gf = g.astype(f32)
        xhat = (x.astype(f32) - mean) * r
        h = gf * scale.astype(f32)
        with jax.named_scope(LN_STATS_SCOPE):
            h_mean = h.sum(axis=1, keepdims=True) / n
            hx_mean = (h * xhat).sum(axis=1, keepdims=True) / n
        dx = r * (h - h_mean - xhat * hx_mean)
        return (dx.astype(x.dtype),
                (gf * xhat).sum(axis=0).astype(scale.dtype),
                gf.sum(axis=0).astype(scale.dtype))

    ln.defvjp(fwd, bwd)
    return ln(x, scale, shift)


def running_update(running_mean: jax.Array, running_var: jax.Array,
                   mean: jax.Array, var: jax.Array, count: int,
                   momentum: float = BN_MOMENTUM):
    """The running statistics after one training step:
    ``(1 - momentum) * running + momentum * batch``, the variance from
    the unbiased estimate ``var * count / (count - 1)``."""
    unbiased = var * (count / max(count - 1, 1))
    return ((1.0 - momentum) * running_mean + momentum * mean,
            (1.0 - momentum) * running_var + momentum * unbiased)

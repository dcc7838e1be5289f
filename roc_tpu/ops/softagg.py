"""Softmax-weighted neighbour aggregation with detached weights: the
elementwise half.

GENConv's ``softmax_sg`` aggregator (Li, Xiong, Thabet, Ghanem,
*DeeperGCN*, arXiv:2006.07739; ``deep_gcns_torch``'s ``GenMessagePassing``
with ``aggr='softmax_sg'``) sums a vertex's in-neighbours' messages
``m_u = relu(z_u) + eps`` under per-channel softmax weights computed
with no gradient::

    out_v = sum_{u in N(v)} sg(w_vu) * m_u
    w_vu  = exp(t m_u) / sum_{u' in N(v)} exp(t m_u')

The weight of an edge depends on its source alone, up to the
destination's normalizer: with ``e_u = exp(t (m_u - c))`` for ANY shift
``c`` that is the same for every source a destination sees, ``w_vu =
e_u / den_v`` and ``den_v = sum_u e_u`` — ``c`` cancels exactly.  So
the forward is ONE sum aggregation of the ``[V, 2F]`` table ``[e * m,
e]`` and a division, and the published backward (the weights are
constants: ``dL/dm_u = sum_{v: u in N(v)} w_vu g_v = e_u * sum_v g_v /
den_v``) ONE sum aggregation of ``g / den`` over the transposed graph
and a product with ``e`` — both on whatever sum layout the graph
resolved to (``GraphContext.soft_aggregate`` drives the scans; this
module is the arithmetic around them, all under ``roc.sagg.weights``).

The shift is the per-channel maximum of ``m`` over every real vertex:
``e <= 1``, nothing overflows, and a channel's largest message keeps
``e = 1`` (an ``e`` that underflows to 0 needs ``t (c - m) > 87``; at
the published ``t = 0.1`` that is a spread of 870 inside one channel).
That ``e <= 1`` holds only if the pass that takes the maximum and the
pass that builds the table see the SAME ``z`` — and on the TPU they
need not: XLA's excess precision lets a fusion read a bfloat16
activation's float32 producer unrounded, so one pass saw ``z`` rounded
and the other did not, ``m - c`` came out positive by a bfloat16 ulp of
``|z|``, and in the untrained model's evaluation (an unnormalized
stream of 5e5) ``exp(0.1 * 1000)`` overflowed: inf in the table, NaN in
1,031 rows of logits, while every op *alone* was finite (PERF.md
section 6, PR 40).  So :func:`as_stored` pins the input to its own
dtype's values once, for both passes, and the exponent is capped at 0
besides.

Two choices were measurements on the chip, not taste (PERF.md section
6, PR 40: the op alone at ogbn-arxiv's shape, ms forward; median row
against a float32 edge-list evaluation): numerator and denominator ride
ONE ``2F``-lane gather of ``[e * m, e]`` (30.49) and not two ``F``-lane
ones (33.56); the table is stored and gathered in the compute dtype
(bfloat16 under ``--dtype mixed``: 0.0022) and not in float32 (52.97,
0.0016).  The losing forms are not kept.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.scopes import SAGG_WEIGHTS_SCOPE

# A denominator under float32's smallest normal number counts as none:
# the TPU flushes a subnormal operand to zero in arithmetic but compares
# it as positive, so ``den > 0`` lets a subnormal ``den`` through to a
# reciprocal of zero and ``0 * inf``.  Measured on the untrained model's
# evaluation (PR 40, the whole eval program on the chip): 5,250 rows of
# logits NaN under ``den > 0``, none under this guard — and the CPU,
# which keeps subnormals, never showed it.  Such a row is past the
# one-table form's exact range anyway (module docstring): every source
# it sees has underflowed.
DEN_MIN = 1.1754944e-38


def as_stored(z: jax.Array) -> jax.Array:
    """``z`` at the values its dtype can hold, whatever precision the
    fusion that produced it computed in: ``reduce_precision`` is an
    operation XLA may not elide, where the ``convert`` pair around a
    bfloat16 intermediate is one it does (module docstring).  The
    identity on float32."""
    info = jnp.finfo(z.dtype)
    return jax.lax.reduce_precision(z, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def messages(z: jax.Array, eps: float) -> jax.Array:
    """``m = relu(z) + eps``, float32."""
    return jax.nn.relu(z.astype(jnp.float32)) + eps


def channel_max(z: jax.Array, eps: float, valid, gather) -> jax.Array:
    """The shift: ``max_v m_v`` per channel over the real rows of every
    partition, ``[F]`` float32.  ``gather`` is the halo's all-gather
    (identity on one device), here over one ``[1, F]`` row a
    partition."""
    with jax.named_scope(SAGG_WEIGHTS_SCOPE):
        m = messages(z, eps)
        if valid is not None:
            m = jnp.where(valid[:, None], m, 0.0)    # m > 0 everywhere
        return gather(m.max(axis=0, keepdims=True)).max(axis=0)


def table(z: jax.Array, c: jax.Array, t: float, eps: float) -> jax.Array:
    """``[e * m, e]`` side by side, ``[rows, 2F]``, with ``e = exp(t (m
    - c))`` computed in float32 and the table rounded to ``z``'s
    dtype."""
    with jax.named_scope(SAGG_WEIGHTS_SCOPE):
        m = messages(z, eps)
        e = jnp.exp(jnp.minimum(t * (m - c), 0.0))
        return jnp.concatenate([e * m, e], axis=1).astype(z.dtype)


def combine(z: jax.Array, num: jax.Array, den: jax.Array):
    """``z + num / den`` (a vertex without a stored in-edge, or whose
    every source underflowed — ``den < DEN_MIN`` — aggregates nothing),
    rounded to ``z``'s dtype; and ``den`` in float32, which the
    backward keeps."""
    with jax.named_scope(SAGG_WEIGHTS_SCOPE):
        den = den.astype(jnp.float32)
        some = den >= DEN_MIN
        agg = jnp.where(some, num.astype(jnp.float32)
                        / jnp.where(some, den, 1.0), 0.0)
        return (z.astype(jnp.float32) + agg).astype(z.dtype), den


def cotangent_over_den(g: jax.Array, den: jax.Array) -> jax.Array:
    """``g / den`` per destination — what the backward's one sum
    aggregation gathers — in ``g``'s dtype."""
    with jax.named_scope(SAGG_WEIGHTS_SCOPE):
        some = den >= DEN_MIN
        q = jnp.where(some, g.astype(jnp.float32)
                      / jnp.where(some, den, 1.0), 0.0)
        return q.astype(g.dtype)


def input_cotangent(g: jax.Array, r: jax.Array, z: jax.Array,
                    c: jax.Array, t: float, eps: float) -> jax.Array:
    """``dz = g + 1[z > 0] * e * r`` with ``r = A^T (g / den)``: the
    self term of ``z + ...`` and the published rule through ``relu``;
    ``e`` is computed again from ``z`` and the kept shift."""
    with jax.named_scope(SAGG_WEIGHTS_SCOPE):
        e = jnp.exp(jnp.minimum(t * (messages(z, eps) - c), 0.0))
        dm = jnp.where(z > 0, e * r.astype(jnp.float32), 0.0)
        return (g.astype(jnp.float32) + dm).astype(z.dtype)

"""Adam optimizer with reference-parity semantics.

Reference (``optimizer.h:34-50``, ``optimizer.cc:79-85``,
``optimizer_kernel.cu:43-103``):

- ``next()`` is called before each update step:
  ``beta1_t *= beta1; beta2_t *= beta2;
  alpha_t = alpha * sqrt(1 - beta2_t) / (1 - beta1_t)``.
- Per-parameter update: ``gt = grad + weight_decay * W`` (L2-coupled,
  fast.ai-style, ``optimizer_kernel.cu:56``), ``m/v`` EMA, then
  ``W -= alpha_t * mt / (sqrt(vt) + eps)``.
- The gradient "allreduce" sums the per-partition replicas on one GPU
  (``optimizer_kernel.cu:88-94``); in the TPU framework the replicas never
  materialize — each shard contributes its local gradient and a ``psum``
  over the mesh produces the identical sum (fp32 addition order aside).

Implemented as pure pytree functions (optax-style) so the whole step jits
and the m/v state shards with the params.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..obs.scopes import EMBED_PARAM_PREFIX, OPT_EMBED_SCOPE


class AdamState(NamedTuple):
    step: jax.Array      # int32 scalar
    beta1_t: jax.Array   # float32 scalar, beta1^step
    beta2_t: jax.Array   # float32 scalar
    m: Any               # pytree like params
    v: Any               # pytree like params


@dataclass(frozen=True)
class AdamConfig:
    # defaults mirror AdamOptimizer ctor defaults (optimizer.h:36-38)
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    weight_decay: float = 0.0


def adam_init(params: Any) -> AdamState:
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    return AdamState(step=jnp.zeros((), jnp.int32),
                     beta1_t=jnp.ones((), jnp.float32),
                     beta2_t=jnp.ones((), jnp.float32),
                     m=zeros,
                     v=jax.tree_util.tree_map(jnp.copy, zeros))


def adam_update(params: Any, grads: Any, state: AdamState, lr: jax.Array,
                cfg: AdamConfig) -> Tuple[Any, AdamState]:
    """One optimizer step.  ``lr`` is the (possibly decayed) base alpha;
    bias correction is applied inside, matching ``next()`` +
    ``adam_update``."""
    beta1_t = state.beta1_t * cfg.beta1
    beta2_t = state.beta2_t * cfg.beta2
    alpha_t = lr * jnp.sqrt(1.0 - beta2_t) / (1.0 - beta1_t)

    def upd(w, g, m, v):
        w32 = w.astype(jnp.float32)
        # L2-coupled decay on weight MATRICES only (the reference's
        # params are all matrices, optimizer_kernel.cu:52-62); scalar
        # params (GIN's learnable eps) are excluded — decaying them
        # would regularize eps back to GIN-0 against the paper's
        # free epsilon
        wd = cfg.weight_decay if w.ndim > 0 else 0.0
        gt = g.astype(jnp.float32) + wd * w32
        mt = cfg.beta1 * m + (1.0 - cfg.beta1) * gt
        vt = cfg.beta2 * v + (1.0 - cfg.beta2) * gt * gt
        new_w = w32 - alpha_t * mt / (jnp.sqrt(vt) + cfg.epsilon)
        return new_w.astype(w.dtype), mt, vt

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    embeds = set()
    if isinstance(params, dict):
        # a typed model's embedding tables update under roc.opt.embed
        # (nested in the caller's roc.opt): metadata only
        embeds = {i for i, k in enumerate(sorted(params))
                  if str(k).startswith(EMBED_PARAM_PREFIX)}

    def scoped(i, *leaf):
        if i not in embeds:
            return upd(*leaf)
        with jax.named_scope(OPT_EMBED_SCOPE):
            return upd(*leaf)

    out = [scoped(i, w, g, m, v) for i, (w, g, m, v) in
           enumerate(zip(flat_p, flat_g, flat_m, flat_v))]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamState(step=state.step + 1, beta1_t=beta1_t,
                            beta2_t=beta2_t, m=new_m, v=new_v)


def decayed_lr(base_lr: float, epoch: jax.Array, decay_rate: float,
               decay_steps: int) -> jax.Array:
    """Staircase lr decay: the reference multiplies ``alpha`` by
    ``decay_rate`` every ``decay_steps`` epochs (``gnn.cc:100-101``)."""
    k = (epoch // jnp.maximum(decay_steps, 1)).astype(jnp.float32)
    return base_lr * jnp.power(decay_rate, k)

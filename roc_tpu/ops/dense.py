"""Dense ops: linear, activations, elementwise, dropout.

The reference implements these as cuBLAS/cuDNN leaf tasks (``linear.cc`` /
``linear_kernel.cu``, ``activation_kernel.cu``, ``element_kernel.cu``,
``dropout_kernel.cu``).  On TPU they are single XLA ops that the compiler
fuses and lowers onto the MXU/VPU — the fused linear+ReLU of
``linear_kernel.cu:81-104`` falls out of XLA fusion for free.

Semantics parity notes:
- Linear: ``y = x @ W``, bias-free by default, exactly the reference
  (``linear_kernel.cu:76-80`` computes W^T·X in its column-major layout,
  which is X·W in our row-major layout).  Optional fused activation
  mirrors ``ActiMode`` (``gnn.h:82-86``).  ``bias=`` adds a row vector
  to the fp32 accumulator before the output cast: beyond the reference,
  and read by the typed models' per-kind root products
  (``models/rgcn.py``) and by the builder's ``linear`` op where a
  family asks for one (``Model.linear(bias=True)``:
  ``models/deepergcn.py``); every other ``linear`` passes none.
- Dropout: inverted dropout with scale 1/(1-rate) in train mode (cuDNN's
  convention, ``dropout_kernel.cu:98-99``), identity in infer mode
  (``dropout_kernel.cu:160-180``).  We thread an explicit PRNG key —
  the functional replacement for the cuDNN dropout states cached in the
  reference's ResourceManager.
- Element add: used for residual connections when the model is deeper
  than 3 layers (``gnn.cc:86-90``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# ActiMode mirror (gnn.h:82-86); ELU is an extension beyond the
# reference's cuDNN set, used by the GAT model family (models/gat.py)
AC_MODE_NONE = "none"
AC_MODE_RELU = "relu"
AC_MODE_SIGMOID = "sigmoid"
AC_MODE_ELU = "elu"

_ACTIVATIONS = {
    AC_MODE_NONE: lambda x: x,
    AC_MODE_RELU: jax.nn.relu,
    AC_MODE_SIGMOID: jax.nn.sigmoid,
    AC_MODE_ELU: jax.nn.elu,
}


def linear(x: jax.Array, w: jax.Array,
           activation: str = AC_MODE_NONE,
           precision=None, bias: Optional[jax.Array] = None) -> jax.Array:
    """x: [V, in_dim] @ w: [in_dim, out_dim] with optional fused
    activation.  Always accumulates in fp32 on the MXU; for fp32 inputs
    the multiply also runs at full precision (parity with the reference's
    fp32 cuBLAS GEMM, ``linear_kernel.cu:76-80``), while bf16 inputs use
    the MXU's native bf16 multiply path.  ``bias`` ([out_dim]) is added
    to the fp32 accumulator, before the cast to ``x``'s dtype."""
    if precision is None and x.dtype == jnp.float32:
        precision = jax.lax.Precision.HIGHEST
    y = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return _ACTIVATIONS[activation](y.astype(x.dtype))


def segment_linear(x: jax.Array, in_bounds, out_rows, terms, ws,
                   bs=None) -> jax.Array:
    """Block-row products over row segments — the per-relation and
    per-kind products of a typed model (``models/builder.py``
    ``rel_linear`` / ``root_linear``).  ``in_bounds`` ``[(lo, hi),
    ...]`` cuts ``x``'s rows into input segments (two may be the same
    rows), ``out_rows`` gives the heights of the output's segments, in
    order; term ``t = (i, j)`` adds ``x[in_bounds[i]] @ ws[t]`` into
    output segment ``j`` (as tall as the input segment), and ``bs[j]``,
    where given, is segment ``j``'s bias.  A segment no term writes is
    zeros.

    The gradient is written by hand: autodiff transposes every row
    slice into a pad to the whole array and adds the pads up — seven
    whole-height copies of a stacked tensor for seven relations.  Here
    ``dx`` is assembled once, by concatenation where the input
    segments tile ``x`` (they do for a stacked input and for the
    per-kind root product) and by one pad a kind otherwise; ``dW_t =
    x_i^T dy_j`` and ``db_j`` are fp32 accumulations of their
    segments."""
    terms = tuple((int(i), int(j)) for i, j in terms)
    in_bounds = tuple((int(lo), int(hi)) for lo, hi in in_bounds)
    out_rows = tuple(int(n) for n in out_rows)
    out_lo = [sum(out_rows[:j]) for j in range(len(out_rows))]
    has_b = bs is not None

    def run(x, ws, bs):
        blocks = {}
        for (i, j), w in zip(terms, ws):
            lo, hi = in_bounds[i]
            y = jax.lax.dot_general(
                x[lo:hi], w, (((1,), (0,)), ((), ())),
                precision=_precision(x),
                preferred_element_type=jnp.float32)
            blocks[j] = y if j not in blocks else blocks[j] + y
        out = []
        for j, n in enumerate(out_rows):
            y = blocks.get(j)
            if y is None:
                y = jnp.zeros((n, ws[0].shape[1]), jnp.float32)
            if has_b:
                y = y + bs[j].astype(jnp.float32)
            out.append(y.astype(x.dtype))
        return jnp.concatenate(out, axis=0)

    @jax.custom_vjp
    def seg(x, ws, bs):
        return run(x, ws, bs)

    def fwd(x, ws, bs):
        return run(x, ws, bs), (x, ws)

    def bwd(res, g):
        x, ws = res
        dws, dx = [], {}
        for (i, j), w in zip(terms, ws):
            lo, hi = in_bounds[i]
            gj = g[out_lo[j]:out_lo[j] + out_rows[j]]
            dws.append(jax.lax.dot_general(
                x[lo:hi], gj, (((0,), (0,)), ((), ())),
                precision=_precision(x),
                preferred_element_type=jnp.float32).astype(w.dtype))
            d = jax.lax.dot_general(
                gj, w, (((1,), (1,)), ((), ())),
                precision=_precision(x),
                preferred_element_type=jnp.float32)
            dx[i] = d if i not in dx else dx[i] + d
        # the distinct input segments, in row order; rows no segment
        # covers (or a segment no term reads) get zeros
        spans = sorted({b for b in in_bounds})
        parts, at = [], 0
        for lo, hi in spans:
            if lo > at:
                parts.append(jnp.zeros((lo - at, x.shape[1]), x.dtype))
            ds = [dx[i] for i, b in enumerate(in_bounds)
                  if b == (lo, hi) and i in dx]
            parts.append(sum(ds[1:], ds[0]).astype(x.dtype) if ds else
                         jnp.zeros((hi - lo, x.shape[1]), x.dtype))
            at = hi
        if at < x.shape[0]:
            parts.append(jnp.zeros((x.shape[0] - at, x.shape[1]),
                                   x.dtype))
        dbs = None
        if has_b:
            dbs = tuple(
                g[out_lo[j]:out_lo[j] + n].astype(jnp.float32)
                .sum(axis=0).astype(bs[j].dtype)
                for j, n in enumerate(out_rows))
        return jnp.concatenate(parts, axis=0), tuple(dws), dbs

    seg.defvjp(fwd, bwd)
    return seg(x, tuple(ws), tuple(bs) if has_b else None)


def _precision(x: jax.Array):
    return (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else None)


def linear_chunked(x: jax.Array, w: jax.Array,
                   activation: str = AC_MODE_NONE,
                   block: int = 65536,
                   bias: Optional[jax.Array] = None) -> jax.Array:
    """:func:`linear` evaluated as a ``lax.scan`` over ``block``-row
    vertex chunks — the chunked output head (models/builder.py,
    ``TrainConfig.head_chunk``).  The compiled matmul body is
    ``[block, in] @ [in, out]`` regardless of ``V``, so the
    classification head stops compiling at full ``[V_p, C]`` width
    into the step and its program is small and shape-stable; the
    ``block`` default matches the streamed head's 65536-row staging
    blocks (core/streaming.py StreamedHead), whose machinery this is
    the in-jit twin of.  Values and input gradients are bit-identical
    to :func:`linear`: each output row's dot product (and each dX
    row's) reads the full ``in`` axis either way, and padding rows
    are sliced back off.  The weight gradient dW sums the row axis
    blockwise across scan iterations — a different (equally valid)
    fp reduction order than the one-matmul reference, so dW matches
    to fp32 roundoff (~1e-7 relative), not bit-for-bit."""
    V, in_dim = x.shape
    n = -(-V // block)
    if n <= 1:
        return linear(x, w, activation, bias=bias)
    vp = n * block
    xp = jnp.pad(x, ((0, vp - V), (0, 0))) if vp != V else x

    def body(_, xb):
        return None, linear(xb, w, activation, bias=bias)

    _, yb = jax.lax.scan(body, None, xp.reshape(n, block, in_dim))
    return yb.reshape(vp, -1)[:V]


def activation(x: jax.Array, mode: str) -> jax.Array:
    return _ACTIVATIONS[mode](x)


def element_add(a: jax.Array, b: jax.Array) -> jax.Array:
    return a + b


def element_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    return a * b


def dropout(x: jax.Array, rate: float, key: Optional[jax.Array],
            train: bool) -> jax.Array:
    """Inverted dropout; identity when not training or rate == 0."""
    if not train or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, p=keep, shape=x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

"""Probe ``attention_precision``: would the cell's tolerances catch an
attention computed in a lower precision than the configuration states?

The plain reference (``references/gat.py``) is run again on the
parameters the window produced, three times, each time with one part of
it moved to the precision in question, and each result is held to the
float32 reference by the cell's own ``correct`` tolerances, exactly as
the system's logits are (``reference.compare``, the loss on the
logits):

* ``as_configured``: what ``--dtype mixed`` states — parameters,
  features and every stored activation rounded to bfloat16; scores,
  softmax and the accumulation of the weighted sum in float32.  It must
  PASS: if it does not, the probe is wrong, not the tolerance.
* ``softmax_bf16``: the same, with every operation of the softmax — the
  two scores, their sum, LeakyReLU, the shift by the row max, ``exp``,
  and each addition into the denominator — rounded to bfloat16.  It
  must FAIL at least one tolerance.
* ``numerator_bf16``: as configured, with the weighted sum accumulated
  in bfloat16: rounded after each edge's addition, as a scan that keeps
  its carry in bfloat16 rounds it.  It must FAIL at least one tolerance.

Rounding is ``lax.reduce_precision`` to bfloat16's 8 exponent and 7
mantissa bits on float32 values, after every single operation that is
meant to be in bfloat16.  A ``convert`` to bfloat16 does not do: on a
TPU XLA computes a fused chain of bfloat16 operations in float32 and
rounds once at its end, and its scatter-add accumulates bfloat16
operands in float32 (PR 29's first chip run: variants written with
``astype`` read 0.010 and 0.004 at worst, where the same code on the
CPU read 1.0 and 0.33).  An accumulation in bfloat16 is sequential by
nature, so :func:`accumulate` walks each row's stored edges in order,
all rows at once, in stages over the rows that still have an edge left
(rows sorted by degree: about twice the edges' work in all).

``as_the_program`` is the system's own logits against the same
reference, for the record.  Run with ``--probe attention_precision`` on
a cell whose configuration's reference is ``gat``; prints one
``{"probe": ...}`` line, ``ok`` true when all three came out as they
must.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

MUST_PASS = {"as_configured": True, "softmax_bf16": False,
             "numerator_bf16": False}


def held_to(tol: Dict[str, float], got: Dict[str, float], loss: float,
            ref_loss: float) -> Dict[str, bool]:
    """Which of the cell's tolerances ``got`` (``reference.compare``)
    and ``loss`` keep, as ``drivers/train_job.py check_reference``
    applies them."""
    return {
        "row_rel_l2_max": got["row_rel_l2_max"] <= tol["row_rel_l2_max"],
        "row_rel_l2_median":
            got["row_rel_l2_median"] <= tol["row_rel_l2_median"],
        "loss": (abs(loss - ref_loss) <= tol["loss_rel"]
                 * max(abs(ref_loss), 1e-9)
                 or abs(loss - ref_loss) <= tol["loss_abs"])}


def bf16(a):
    """``a`` (float32) rounded to bfloat16's precision, still float32."""
    import jax
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _same(a):
    return a


class Rows:
    """The stored rows on the host, sorted by degree, largest first:
    stage ``(lo, hi, n)`` is edge positions ``lo..hi-1`` of the ``n``
    rows that have more than ``lo`` edges."""

    def __init__(self, row_ptr: np.ndarray, col_idx: np.ndarray):
        deg = np.diff(row_ptr).astype(np.int32)
        self.order = np.argsort(-deg, kind="stable")
        self.start = row_ptr[:-1][self.order].astype(np.int32)
        self.deg = deg[self.order]
        self.src = col_idx.astype(np.int32)
        self.dst = np.repeat(np.arange(deg.shape[0], dtype=np.int32), deg)
        self.stages = []
        lo, top = 0, int(deg.max()) if deg.size else 0
        while lo < top:
            hi = min(max(1, 2 * lo), top)
            self.stages.append((lo, hi, int((deg > lo).sum())))
            lo = hi


@functools.lru_cache(maxsize=None)
def _stage(rnd):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(acc, lo, hi, start, deg, src, w, z):
        n, K = acc.shape[0], w.shape[1]

        def body(j, acc):
            valid = j < deg
            e = jnp.where(valid, start + j, 0)
            part = (w[e][:, :, None] * z[src[e]].reshape(n, K, -1)
                    ).reshape(n, -1)
            return rnd(acc + jnp.where(valid[:, None], part, 0.0))

        return jax.lax.fori_loop(lo, hi, body, acc)
    return run


def accumulate(rows: Rows, w, z, rnd=_same):
    """``out[i] = sum over row i's stored edges e, in order, of w[e] *
    z[src[e]]`` per head (``w`` [E, K], ``z`` [V, K*d]), with ``rnd``
    applied to the running sum after every addition."""
    import jax.numpy as jnp
    acc = jnp.zeros((rows.order.shape[0], z.shape[1]), jnp.float32)
    src = jnp.asarray(rows.src)
    for lo, hi, n in rows.stages:
        sub = _stage(rnd)(acc[:n], lo, hi, jnp.asarray(rows.start[:n]),
                          jnp.asarray(rows.deg[:n]), src, w, z)
        acc = acc.at[:n].set(sub)
    return jnp.zeros_like(acc).at[jnp.asarray(rows.order)].set(acc)


def edge_softmax_sum(rows: Rows, z, s, t, score=_same, acc=_same,
                     neg_slope: float = 0.2):
    """The reference's edge softmax over the host's rows, ``score``
    applied after every operation of the softmax and ``acc`` after every
    addition into the numerator."""
    import jax
    import jax.numpy as jnp
    V, K = s.shape
    src, dst = jnp.asarray(rows.src), jnp.asarray(rows.dst)
    s, t = score(s), score(t)
    e = score(s[src] + t[dst])
    e = jnp.where(e > 0, e, score(neg_slope * e))
    m = jax.ops.segment_max(e, dst, num_segments=V,
                            indices_are_sorted=True)
    m = jnp.where(jnp.isfinite(m), m, 0)
    w = score(jnp.exp(score(e - m[dst])))
    den = accumulate(rows, w, jnp.ones((V, K), jnp.float32), score)
    num = accumulate(rows, w, z, acc)
    den = jnp.maximum(den, jnp.finfo(jnp.float32).tiny)
    return (num.reshape(V, K, -1) / den[:, :, None]).reshape(z.shape)


def probe(run) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import reference
    cfg, tol = run.cell.config, run.cell.extras["correct"]
    gat = run.cell.module("references", cfg["reference"])
    d = run.data
    rows = Rows(d.row_ptr, d.col_idx)

    def jitted(forward):
        return reference.run(forward, run.scratch["params"], d.features,
                             d.labels, d.mask, d.row_ptr, d.col_idx,
                             cfg["model"])

    def staged(**rnd):
        """The forward with the staged edge softmax: not one program
        (a stage's height is read off the host's rows), so run op by
        op."""
        with jax.default_matmul_precision("highest"):
            params = {k: jnp.asarray(v, jnp.float32)
                      for k, v in run.scratch["params"].items()}
            logits = gat.forward(
                params, jnp.asarray(d.features, jnp.float32), None,
                cfg["model"], stored=bf16,
                attend=lambda z, s, t, _graph: edge_softmax_sum(
                    rows, z, s, t, **rnd))
            loss = reference.loss_sum(
                logits, jnp.asarray(d.labels, jnp.int32),
                jnp.asarray(d.mask, jnp.int32))
        return {"logits": np.asarray(logits, np.float32),
                "loss": float(loss)}

    ref = jitted(gat.forward)
    configured = jitted(functools.partial(gat.forward, stored=bf16))
    variants = {
        "as_configured": lambda: configured,
        "softmax_bf16": lambda: staged(score=bf16),
        "numerator_bf16": lambda: staged(acc=bf16)}
    out: Dict[str, Any] = {
        "tolerances": {k: v for k, v in tol.items() if k != "reason"},
        "reference_loss": ref["loss"], "variants": {}}
    ok = True
    for name, make in variants.items():
        got = make()
        row = reference.compare(got["logits"], ref["logits"])
        kept = held_to(tol, row, got["loss"], ref["loss"])
        passes = row["finite"] and all(kept.values())
        ok = ok and passes == MUST_PASS[name]
        out["variants"][name] = {
            **row, "loss": got["loss"], "keeps": kept, "passes": passes,
            "must_pass": MUST_PASS[name]}
    out["as_the_program"] = reference.compare(
        np.asarray(run.scratch["logits"], dtype=np.float32), ref["logits"])
    out["ok"] = ok
    return out

"""Probe ``gtrans_precision``: would the cell's tolerances catch a Graph
Transformer computed in a lower precision than the configuration
states?

The plain reference (``references/gtrans.py``) is run again on the
parameters the window produced, each time with one part of it moved to
the precision in question, and each result is held to the float32
reference by the cell's own ``correct`` tolerances, exactly as the
system's logits are (``reference.compare``, the loss on the logits) —
the pattern of ``probes/attention_precision.py``, whose staged per-row
accumulation (``Rows``, ``accumulate``) and rounding (``bf16``:
``lax.reduce_precision`` after every single operation, because XLA
computes a fused chain of bfloat16 operations in float32) it takes
through the cell's own module lookup:

* ``as_configured``: what ``--dtype mixed`` states — the weight
  matrices, biases and gates, the features, ``q``, ``[k | v]``, ``r``
  and every stored activation rounded to bfloat16; the scores, the
  softmax, the weighted sum, the gate's logit and LayerNorm in float32.
  It must PASS: if it does not, the probe is wrong, not the tolerance.
* ``softmax_bf16``: the same, with every operation of the softmax — the
  score, the shift by the row max, ``exp``, and each addition into the
  denominator — rounded to bfloat16.  It must FAIL at least one
  tolerance.
* ``weighted_sum_bf16``: as configured, with the weighted sum of the
  values accumulated in bfloat16: rounded after each stored edge's
  addition, as a scan that keeps its carry in bfloat16 rounds it.  It
  must FAIL at least one tolerance.

``as_the_program`` is the system's own logits against the same
reference, for the record.  Run with ``--probe gtrans_precision`` on a
cell whose configuration's reference is ``gtrans``; prints one
``{"probe": ...}`` line, ``ok`` true when every variant came out as it
must.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

MUST_PASS = {"as_configured": True, "softmax_bf16": False,
             "weighted_sum_bf16": False}


def _same(a):
    return a


def staged_attention(tools, rows, score=_same, acc=_same):
    """The reference's edge attention over the host's ``rows``, ``score``
    applied after every operation of the softmax and ``acc`` after
    every addition into the weighted sum."""
    import jax
    import jax.numpy as jnp
    src, dst = jnp.asarray(rows.src), jnp.asarray(rows.dst)

    def attend(q, k, v, _graph, heads, _keep):
        V = q.shape[0]
        d = q.shape[1] // heads
        s = score(jnp.einsum(
            "ekd,ekd->ek", q.reshape(V, heads, d)[dst],
            k.reshape(V, heads, d)[src],
            precision=jax.lax.Precision.HIGHEST) / d ** 0.5)
        mx = jax.ops.segment_max(s, dst, num_segments=V,
                                 indices_are_sorted=True)
        mx = jnp.where(jnp.isfinite(mx), mx, 0)
        w = score(jnp.exp(score(s - mx[dst])))               # [E, K]
        den = tools.accumulate(rows, w, jnp.ones((V, heads), jnp.float32),
                               score)
        num = tools.accumulate(rows, w, v, acc)
        den = jnp.maximum(den, jnp.finfo(jnp.float32).tiny)
        return (num.reshape(V, heads, d) / den[:, :, None]).reshape(
            q.shape)

    return attend


def probe(run) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import reference
    cfg, tol = run.cell.config, run.cell.extras["correct"]
    gt = run.cell.module("references", cfg["reference"])
    tools = run.cell.module("probes", "attention_precision")
    bf16, held_to = tools.bf16, tools.held_to
    d = run.data
    rows = tools.Rows(d.row_ptr, d.col_idx)

    def jitted(forward):
        return reference.run(forward, run.scratch["params"], d.features,
                             d.labels, d.mask, d.row_ptr, d.col_idx,
                             cfg["model"])

    def staged(**rnd):
        """The forward with the staged attention: not one program (a
        stage's height is read off the host's rows), so run op by
        op."""
        with jax.default_matmul_precision("highest"):
            params = {k: jnp.asarray(v, jnp.float32)
                      for k, v in run.scratch["params"].items()}
            logits = gt.forward(
                params, jnp.asarray(d.features, jnp.float32), None,
                cfg["model"], stored=bf16,
                attend=staged_attention(tools, rows, **rnd))
            loss = reference.loss_sum(
                logits, jnp.asarray(d.labels, jnp.int32),
                jnp.asarray(d.mask, jnp.int32))
        return {"logits": np.asarray(logits, np.float32),
                "loss": float(loss)}

    ref = jitted(gt.forward)
    variants = {
        "as_configured": lambda: jitted(functools.partial(
            gt.forward, stored=bf16)),
        "softmax_bf16": lambda: staged(score=bf16),
        "weighted_sum_bf16": lambda: staged(acc=bf16)}
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

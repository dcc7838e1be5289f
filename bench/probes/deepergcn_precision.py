"""Probe ``deepergcn_precision``: would the cell's tolerances catch a
DeeperGCN computed in a lower precision than the configuration states?

The plain reference (``references/deepergcn.py``) is run again on the
parameters and running statistics the window produced, each time with
one part of it moved to the precision in question, and each result is
held to the float32 reference by the cell's own ``correct`` tolerances,
exactly as the system's logits are (``reference.compare``, the loss on
the logits) — the pattern of ``probes/gcn2_precision.py``; the staged
per-row accumulation (``Rows``, ``accumulate``) and the rounding
(``bf16``: ``lax.reduce_precision`` after every single operation,
because XLA computes a fused chain of bfloat16 operations in float32)
come from ``probes/attention_precision.py`` through the cell's own
module lookup:

* ``as_configured``: what ``--dtype mixed`` states — the weight
  matrices, the features and every stored activation rounded to
  bfloat16; every sum over neighbours, every matrix product and the
  whole of BatchNorm (statistics, scale, shift, arithmetic) in float32.
  It must PASS: if it does not, the probe is wrong, not the tolerance.
* ``neighbour_sum_bf16``: as configured, with each of the softmax
  aggregation's two sums over a vertex's stored in-edges (numerator and
  denominator, twenty-eight times) accumulated in bfloat16: the running
  sum of a row rounded after each stored edge's addition, as a scan
  that keeps its accumulator in bfloat16 rounds it.  The nearest
  precision below the stated one.  It must FAIL at least one tolerance.
* ``batch_norm_bf16``: as configured, with BatchNorm's inference
  arithmetic in bfloat16: the running mean and variance rounded to it,
  as statistics kept in bfloat16 would be, and every operation of the
  normalization (the difference, the square root's argument, the
  quotient, scale, shift) rounded after it.  Recorded, and it PASSES,
  for what that teaches (the chip, PR 40: median 0.0037 against
  0.0031 as configured, worst row 0.0095 against 0.0083): between two
  activations that are stored in bfloat16 anyway, a normalization
  rounded per operation adds a fifth to the median and nothing to the
  worst row — no limit with room on both sides separates it.  What
  would be ruinous, moments *taken* in bfloat16 (a running sum over
  169,343 rows sticks at 256 times an addend), is a property of the
  train step; this comparison hands the reference the statistics the
  program made, so it cannot see how they were taken (PERF.md section
  7), and ``tests/test_deepergcn_reference.py`` holds the program's
  moments to float32 instead.

``as_the_program`` is the system's own logits against the same
reference, for the record.  Run with ``--probe deepergcn_precision`` on
a cell whose configuration's reference is ``deepergcn``; prints one
``{"probe": ...}`` line, ``ok`` true when every variant came out as it
must.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

MUST_PASS = {"as_configured": True, "neighbour_sum_bf16": False,
             "batch_norm_bf16": True}


def probe(run) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import reference
    cfg, tol = run.cell.config, run.cell.extras["correct"]
    ref_mod = run.cell.module("references", cfg["reference"])
    tools = run.cell.module("probes", "attention_precision")
    bf16, held_to = tools.bf16, tools.held_to
    d = run.data
    rows = tools.Rows(d.row_ptr, d.col_idx)

    # what the program stores in bfloat16: features, activations and
    # the compute copies of the ``linear`` weights and biases (the
    # reference hands BatchNorm's vectors to ``norm`` unrounded)
    stored = bf16

    def jitted(forward):
        return reference.run(
            forward, run.scratch["params"], d.features, d.labels, d.mask,
            d.row_ptr, d.col_idx, cfg["model"])

    def staged_sums():
        """The forward with the staged softmax sums: not one program (a
        stage's height is read off the host's rows), so run op by
        op."""
        src, dst = jnp.asarray(rows.src), jnp.asarray(rows.dst)
        V = int(d.row_ptr.shape[0] - 1)

        def sums(m, logit, _graph):
            top = jax.ops.segment_max(logit[src], dst, num_segments=V,
                                      indices_are_sorted=True)
            e = bf16(jnp.exp(logit[src] - top[dst]))        # [E, F]
            return (tools.accumulate(rows, e, bf16(m), bf16),
                    tools.accumulate(rows, e, jnp.ones_like(m), bf16))

        with jax.default_matmul_precision("highest"):
            params = {k: jnp.asarray(v, jnp.float32)
                      for k, v in run.scratch["params"].items()}
            logits = ref_mod.forward(
                params, jnp.asarray(d.features, jnp.float32), None,
                cfg["model"], stored=stored, sums=sums)
            loss = reference.loss_sum(
                logits, jnp.asarray(d.labels, jnp.int32),
                jnp.asarray(d.mask, jnp.int32))
        return {"logits": np.asarray(logits, np.float32),
                "loss": float(loss)}

    def norm_bf16(x, p, name):
        mean, var = bf16(p[f"{name}_mean"]), bf16(p[f"{name}_var"])
        xhat = bf16(bf16(x - mean) / bf16(jnp.sqrt(bf16(var + 1e-5))))
        return bf16(bf16(bf16(p[f"{name}_scale"]) * xhat)
                    + bf16(p[f"{name}_shift"]))

    ref = jitted(ref_mod.forward)
    variants = {
        "as_configured": lambda: jitted(functools.partial(
            ref_mod.forward, stored=stored)),
        "neighbour_sum_bf16": staged_sums,
        "batch_norm_bf16": lambda: jitted(functools.partial(
            ref_mod.forward, stored=stored, norm=norm_bf16))}
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

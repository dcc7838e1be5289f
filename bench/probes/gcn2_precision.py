"""Probe ``gcn2_precision``: would the cell's tolerances catch a GCNII*
computed in a lower precision than the configuration states?

The plain reference (``references/gcn2.py``) is run again on the
parameters the window produced, each time with one part of it moved to
the precision in question, and each result is held to the float32
reference by the cell's own ``correct`` tolerances, exactly as the
system's logits are (``reference.compare``, the loss on the logits) —
the pattern of ``probes/attention_precision.py``, whose staged
per-row accumulation (``Rows``, ``accumulate``) and rounding (``bf16``:
``lax.reduce_precision`` after every single operation, because XLA
computes a fused chain of bfloat16 operations in float32) this probe
borrows through the cell's own module lookup:

* ``as_configured``: what ``--dtype mixed`` states — parameters,
  features and every stored activation rounded to bfloat16; every sum
  over neighbours and every matrix product accumulated in float32.  It
  must PASS: if it does not, the probe is wrong, not the tolerance.
* ``neighbour_sum_bf16``: as configured, with each of the sixteen
  neighbour sums accumulated in bfloat16: the running sum of a row
  rounded after each stored edge's addition, as a scan that keeps its
  accumulator in bfloat16 rounds it.  The nearest precision below the
  stated one.  It must FAIL at least one tolerance.
* ``master_weights_bf16``: as configured, with the parameters rounded
  to bfloat16 before anything reads them — what a job whose *master*
  weights were bfloat16 would hold.  Recorded, and it must PASS, for
  what that teaches: the forward casts the parameters to bfloat16
  anyway, so the logits are ``as_configured``'s to the bit.  What
  bfloat16 master weights lose is the update (lr 0.001 times Adam's
  unit-sized step against a weight of 0.1 is under half a bfloat16
  ulp, 0.0002: most steps round away), and a comparison of one forward
  at given parameters cannot see that (PERF.md section 7).

``as_the_program`` is the system's own logits against the same
reference, for the record.  Run with ``--probe gcn2_precision`` on a
cell whose configuration's reference is ``gcn2``; prints one
``{"probe": ...}`` line, ``ok`` true when every variant came out as it
must.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

MUST_PASS = {"as_configured": True, "neighbour_sum_bf16": False,
             "master_weights_bf16": True}


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

    def jitted(forward, params=None):
        return reference.run(
            forward, run.scratch["params"] if params is None else params,
            d.features, d.labels, d.mask, d.row_ptr, d.col_idx,
            cfg["model"])

    def staged_sum():
        """The forward with the staged neighbour sum: not one program
        (a stage's height is read off the host's rows), so run op by
        op."""
        deg = np.diff(d.row_ptr).astype(np.float32)
        inv = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1.0)), 0.0)
        # the table-baked norm of a stored edge, as the program bakes it
        w = jnp.asarray((inv[rows.dst] * inv[rows.src])[:, None])

        def propagate(h, _graph):
            return tools.accumulate(rows, bf16(w), h, bf16)

        with jax.default_matmul_precision("highest"):
            params = {k: jnp.asarray(v, jnp.float32)
                      for k, v in run.scratch["params"].items()}
            logits = ref_mod.forward(
                params, jnp.asarray(d.features, jnp.float32), None,
                cfg["model"], stored=bf16, propagate=propagate)
            loss = reference.loss_sum(
                logits, jnp.asarray(d.labels, jnp.int32),
                jnp.asarray(d.mask, jnp.int32))
        return {"logits": np.asarray(logits, np.float32),
                "loss": float(loss)}

    ref = jitted(ref_mod.forward)
    configured = functools.partial(ref_mod.forward, stored=bf16)
    rounded = {k: np.asarray(bf16(jnp.asarray(v, jnp.float32)))
               for k, v in run.scratch["params"].items()}
    variants = {
        "as_configured": lambda: jitted(configured),
        "neighbour_sum_bf16": staged_sum,
        "master_weights_bf16": lambda: jitted(configured, rounded)}
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

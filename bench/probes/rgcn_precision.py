"""Probe ``rgcn_precision``: would the cell's tolerances catch an R-GCN
whose relation means were computed in a lower precision than the
configuration states?

The plain reference (``references/rgcn.py``) is run again on the
parameters the window produced, each time with one part of it moved to
the precision in question, and each result is held to the float32
reference by the cell's own ``correct`` tolerances, exactly as the
system's logits are — the pattern of ``probes/gcn2_precision.py``,
with the staged per-row accumulation and the rounding of
``probes/attention_precision.py`` (``Rows``, ``accumulate``, ``bf16``,
``held_to``) borrowed through the cell's own module lookup:

* ``as_configured``: what ``--dtype mixed`` states — parameters,
  embedding rows, features and every stored activation rounded to
  bfloat16; every relation mean and every matrix product accumulated in
  float32.  It must PASS: if it does not, the probe is wrong, not the
  tolerance.
* ``relation_mean_bf16``: as configured, with each of the fourteen
  relation means (seven relations, two layers) accumulated in
  bfloat16: a row's running sum rounded after each stored edge's
  addition, as a scan whose accumulator is bfloat16 rounds it, then
  divided by the relation's in-degree.  The nearest precision below
  the stated one.  It must FAIL at least one tolerance.  Computed on
  the host's CPU (rounding is ``lax.reduce_precision``, the same
  there): the live trainer leaves the chip no room for an eager
  forward.

``as_the_program`` is the system's own logits against the same
reference, for the record.  Run with ``--probe rgcn_precision`` on a
cell whose configuration's reference is ``rgcn``; prints one
``{"probe": ...}`` line, ``ok`` true when every variant came out as it
must.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

MUST_PASS = {"as_configured": True, "relation_mean_bf16": False}


def probe(run) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import reference
    cfg, tol = run.cell.config, run.cell.extras["correct"]
    ref_mod = run.cell.module("references", cfg["reference"])
    tools = run.cell.module("probes", "attention_precision")
    bf16, held_to = tools.bf16, tools.held_to
    d = run.data

    def jitted(forward):
        return reference.run(forward, run.scratch["params"], d.features,
                             d.labels, d.mask, d.row_ptr, d.col_idx,
                             cfg["model"])

    def staged_mean(y, sub, degree):
        """One relation's mean, its rows' sums accumulated edge by edge
        in bfloat16: the relation's own rows are read off the
        sub-graph's arrays (concrete here: this forward runs op by
        op)."""
        src = np.concatenate([np.asarray(sub.src).reshape(-1),
                              np.asarray(sub.tail_src)])
        dst = np.concatenate([np.asarray(sub.dst).reshape(-1),
                              np.asarray(sub.tail_dst)])
        real = src != y.shape[0] - 1          # the appended zero row
        row_ptr = np.zeros(sub.num_nodes + 1, np.int64)
        np.cumsum(np.bincount(dst[real], minlength=sub.num_nodes),
                  out=row_ptr[1:])
        if not real.any():
            return jnp.zeros((sub.num_nodes, y.shape[1]), jnp.float32)
        rows = tools.Rows(row_ptr, src[real])
        total = tools.accumulate(
            rows, jnp.ones((rows.src.shape[0], 1), jnp.float32), y, bf16)
        return jnp.where(degree[:, None] > 0,
                         total / jnp.maximum(degree, 1.0)[:, None], 0.0)

    def staged():
        """Op by op, and on the host: the trainer is still alive on
        the chip (this runs inside its ``inspect``), and an eager
        forward keeps every relation's remapped edge list beside it."""
        g = reference.Graph.from_csr(d.row_ptr, d.col_idx,
                                     widest=max(cfg["model"]["layers"]))
        with jax.default_device(jax.devices("cpu")[0]), \
                jax.default_matmul_precision("highest"):
            graph = reference.Graph(
                *(jnp.asarray(a) for a in g.arrays()), g.num_nodes)
            params = {k: jnp.asarray(v, jnp.float32)
                      for k, v in run.scratch["params"].items()}
            logits = ref_mod.forward(
                params, jnp.asarray(d.features, jnp.float32), graph,
                cfg["model"], stored=bf16, mean=staged_mean)
            loss = reference.loss_sum(
                logits, jnp.asarray(d.labels, jnp.int32),
                jnp.asarray(d.mask, jnp.int32))
        return {"logits": np.asarray(logits, np.float32),
                "loss": float(loss)}

    ref = jitted(ref_mod.forward)
    variants = {
        "as_configured": lambda: jitted(
            functools.partial(ref_mod.forward, stored=bf16)),
        "relation_mean_bf16": staged}
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

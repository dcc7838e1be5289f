"""roc-lint: trace-level static analysis for jaxpr/HLO anti-patterns
plus a rule-driven source lint — regressions against the invariants the
ROC performance story rests on are caught BEFORE merge, not after a
chip run.

Eight levels, mirroring XLA's own cost_analysis / HLO-verifier split:

- :mod:`ast_lint` — source-level rules over the tree (stdout
  discipline, host syncs in hot paths, jits bypassing the compile
  observer);
- :mod:`concurrency_lint` — the host-side threading/signal surface
  (lock-order cycles, signal-handler safety, condvar predicates,
  unguarded shared state, blocking under locks, thread shutdown
  paths, multi-process artifact-lock ownership), jax-free like the
  AST level;
- :mod:`jaxpr_lint` — rules over the ClosedJaxprs of both trainers'
  step functions and the recorded-op model graph (bf16 upcasts,
  host callbacks under jit, large non-donated buffers, cross-shard
  materialization, int32 index-overflow hazards);
- :mod:`hlo_lint` — rules over the optimized HLO text +
  ``cost_analysis`` that ``ObservedJit`` already captures
  (fusion-breaking copies of activation-scale tensors, bytes-accessed
  vs the core/memory.py model);
- :mod:`programspace` — the enumerated compiled-program set and its
  shrink-only ``program_budget`` ratchet;
- :mod:`collective_lint` — SPMD collective choreography at P>=2;
- :mod:`sharding_lint` — sharding propagation over the candidate
  jaxprs: the replication ledger vs ``replication_budget``,
  full-width re-gathers, sharding mismatches, donation under
  sharding, and the (parts, model) mesh-portability report;
- :mod:`protocol_lint` — the protocol auditor & bounded model
  checker: AST-extracted wire vocabulary of the router<->replica
  channels held against :mod:`protocol_specs`'s declared contracts
  (per-kind field sets, unknown-kind rejection), plus
  :mod:`modelcheck`'s exhaustive bounded BFS over crash/interleave
  schedules of the router request lifecycle, the checkpoint v3
  two-phase commit, and the versioned-table swap — jax-free like
  the AST and concurrency levels.

:mod:`driver` assembles the lint units (synthetic dataset, both
trainers, the 8-virtual-device mesh) and runs every rule;
``python -m roc_tpu.analysis`` is the CLI, ratcheted into tier-1 via
``scripts/lint_baseline.json`` (tests/test_analysis.py).
"""

from .findings import Finding, load_baseline, save_baseline, split_findings


def force_cpu_rig() -> None:
    """Force THE 8-virtual-device CPU rig the analysis levels, the
    prewarm CLI, and the prewarm test workers all audit against.
    jax is typically already imported (roc_tpu/__init__ pulls it in),
    so the JAX_PLATFORMS env var alone would be latched-and-ignored —
    the platform goes through jax.config (like tests/conftest.py);
    XLA_FLAGS is still read at CPU-client init, so the virtual-device
    append works as long as this runs before the first device use.
    ONE implementation: a copy missing the device-count flag is how
    the parts=2 rig got silently skipped-and-never-warmed."""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"   # children / consistency
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


__all__ = ["Finding", "force_cpu_rig", "load_baseline",
           "save_baseline", "split_findings"]

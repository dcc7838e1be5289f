"""Assemble lint units and run every rule — the engine behind
``python -m roc_tpu.analysis``.

The trace stage builds BOTH trainers against a small synthetic
dataset (the same 8-virtual-device CPU rig the test tier uses), traces
their train/eval step functions and the recorded-op model graph to
ClosedJaxprs, and compiles the single-device train step once for the
HLO rules.  Mixed precision (fp32 master / bf16 compute) is used so
the bf16-path rules actually arm — the invariants under lint are the
production configs', not float32 toy semantics.

Findings are emitted as ``analysis``-category obs events (JSONL
artifact + machine-readable CI trail) in addition to being returned.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from ..obs.events import emit
from .ast_lint import RULES as AST_RULES, run_ast_lint
from .collective_lint import (COLLECTIVE_RULES, CollectiveUnit,
                              check_ring_halo, run_collective_lint)
from .concurrency_lint import CONCURRENCY_RULES, audit_concurrency
from .findings import Finding, dedupe
from .hlo_lint import check_bytes_model, check_large_copy
from .jaxpr_lint import JAXPR_RULES, JaxprUnit, run_jaxpr_lint
from .programspace import (_C, _DEG, _F, _H, _V, PROGRAMSPACE_RULES,
                           audit_program_space)
from .protocol_lint import PROTOCOL_RULES, audit_protocol
from .sharding_lint import SHARDING_RULES, audit_sharding

HLO_RULES = ("hlo-large-copy", "hlo-bytes-model")

# trace-stage rules that are neither jaxpr- nor hlo- prefixed: they
# inspect the BUILT trainers (here: the distributed trainer's actual
# partition / ring tables), so they need the same 8-virtual-device rig
EXTRA_TRACE_RULES = ("partition-imbalance", "collective-ring-halo")

# a recorded max/mean edge imbalance past this on >1 device means the
# slowest shard gates every SPMD step by >= 50% over the mean — the
# split (or the vertex order feeding it) needs attention
IMBALANCE_THRESHOLD = 1.5


def is_trace_rule(name: str) -> bool:
    """True for rules that need the jax trace/build stage (jaxpr-*,
    hlo-*, collective-*, the program-space and sharding auditors, and
    the built-trainer checks) — shared by the driver's stage gating
    and the CLI's stale-entry scoping."""
    return (name.startswith(("jaxpr-", "hlo-", "collective-"))
            or name in EXTRA_TRACE_RULES
            or name in PROGRAMSPACE_RULES
            or name in SHARDING_RULES)


def check_partition_imbalance(unit: str, real_edges,
                              num_parts: Optional[int] = None,
                              threshold: float = IMBALANCE_THRESHOLD
                              ) -> List[Finding]:
    """[partition-imbalance] warn when the recorded ``max/mean`` edge
    imbalance of a >1-device partition exceeds ``threshold`` — the
    straggler shard would gate every step and every ring hop.  Fed by
    the per-part real edge counts the trainer records in its manifest
    (``partition_static_stats``); baselined through the shrink-only
    ratchet like every other rule."""
    import numpy as np
    real_edges = np.asarray(real_edges, dtype=np.float64)
    if num_parts is None:
        num_parts = int(real_edges.shape[0])
    if num_parts < 2 or real_edges.size == 0:
        return []
    mean = float(real_edges.sum()) / num_parts
    if mean <= 0:
        return []
    ratio = float(real_edges.max()) / mean
    if ratio <= threshold:
        return []
    return [Finding(
        "partition-imbalance", unit,
        f"edge imbalance max/mean {ratio:.2f} > {threshold} across "
        f"{num_parts} devices — the slowest shard gates every SPMD "
        f"step (use --partition cost / --rebalance, or reorder the "
        f"vertex ids)",
        key=f"parts={num_parts}",
        detail={"ratio": round(ratio, 4), "threshold": threshold})]

# synthetic rig: big enough that activation scale ([V, F]) dominates
# class-width tensors ([V, C]) AND per-device activation scale
# (V/8 * F on the mesh) dominates parameter scale (F * H) by the
# margins the rules assume; small enough that the whole stage
# (3 trainer builds + 1 CPU compile) stays inside the tier's <60 s
# budget.  The scale constants (_V/_DEG/_F/_C/_H) are defined ONCE in
# programspace and imported at the top of this module (the reverse
# import would cycle), so the jaxpr-lint stage and the program-space
# auditor can never check different synthetic rigs.


def all_rule_names() -> List[str]:
    return ([r.name for r in AST_RULES] + list(CONCURRENCY_RULES)
            + list(PROTOCOL_RULES) + list(JAXPR_RULES)
            + list(HLO_RULES) + list(EXTRA_TRACE_RULES)
            + list(COLLECTIVE_RULES) + list(PROGRAMSPACE_RULES)
            + list(SHARDING_RULES))


def _needs_trace(select: Optional[List[str]]) -> bool:
    """True when the jaxpr/HLO/collective trainer-build stage must
    run.  Program-space and sharding rules have their own rig builds
    (audit_program_space / audit_sharding) and alone don't need this
    stage."""
    if select is None:
        return True
    return any(is_trace_rule(s) and s not in PROGRAMSPACE_RULES
               and s not in SHARDING_RULES
               for s in select)


def build_trace_findings(select: Optional[List[str]] = None,
                         hlo_factor: float = 32.0) -> List[Finding]:
    """Trace/compile the step functions and run the jaxpr + HLO rules.
    Needs a jax backend (the CLI forces the 8-virtual-device CPU rig);
    import stays inside so the AST-only path never touches jax."""
    import jax
    import jax.numpy as jnp

    from ..core.graph import synthetic_dataset
    from ..models.gcn import build_gcn
    from ..train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(num_nodes=_V, avg_degree=_DEG, in_dim=_F,
                           num_classes=_C, seed=0)
    cfg = TrainConfig(verbose=False, symmetric=True,
                      dtype=jnp.float32, compute_dtype=jnp.bfloat16)
    model = build_gcn([_F, _H, _C], dropout_rate=0.5)
    tr = Trainer(model, ds, cfg)
    key = jax.random.PRNGKey(0)
    lr = jnp.asarray(0.01, jnp.float32)
    donate_min = max(int(v.size) * v.dtype.itemsize
                     for v in jax.tree_util.tree_leaves(tr.params))
    ctx: Dict[str, Any] = dict(
        compute_dtype="bfloat16", num_nodes=_V, vf_elems=_V * _F,
        halo="gather", donate_min_bytes=donate_min)

    units = [
        JaxprUnit("train_step", jax.make_jaxpr(tr._train_step._jit)(
            tr.params, tr.opt_state, key, lr, tr.feats, tr.labels,
            tr.mask, tr.gctx), **ctx),
        JaxprUnit("eval_step", jax.make_jaxpr(tr._eval_step._jit)(
            tr.params, tr.feats, tr.labels, tr.mask, tr.gctx), **ctx),
        # the recorded-op model graph, traced directly (no jit): the
        # builder's interpreter is where an op-list rewrite (fusion,
        # streaming split) would first leak an anti-pattern
        JaxprUnit("model_graph", jax.make_jaxpr(
            lambda p: tr.model.loss_fn(
                p, tr.feats, tr.labels, tr.mask, tr.gctx, key=key,
                train=True))(tr.params), **ctx),
    ]

    # the host-feature streaming tier: its device-resident steps
    # (tail grad + the optimizer apply) are separate dispatch
    # boundaries with their own donation contracts
    str_tr = Trainer(build_gcn([_F, _H, _C], dropout_rate=0.5), ds,
                     TrainConfig(verbose=False, symmetric=True,
                                 features="host",
                                 dtype=jnp.float32,
                                 compute_dtype=jnp.bfloat16))
    y = jnp.zeros((_V, _H), jnp.bfloat16)
    grads = jax.tree_util.tree_map(jnp.zeros_like, str_tr.params)
    units.append(JaxprUnit(
        "tail_grad", jax.make_jaxpr(str_tr._tail_grad._jit)(
            str_tr.params, y, key, str_tr.labels, str_tr.mask,
            str_tr.gctx), **ctx))
    units.append(JaxprUnit(
        "apply_update", jax.make_jaxpr(str_tr._apply_update._jit)(
            str_tr.params, str_tr.opt_state, grads, lr), **ctx))

    if len(jax.devices()) > 1:
        from ..parallel.distributed import DistributedTrainer
        parts = len(jax.devices())
        dtr = DistributedTrainer(
            build_gcn([_F, _H, _C], dropout_rate=0.5), ds, parts,
            TrainConfig(verbose=False, symmetric=True,
                        dtype=jnp.float32,
                        compute_dtype=jnp.bfloat16))
        d = dtr.data
        fuse_tabs = (d.ell_w, d.sect_w, d.ring_w, d.bd_scale)
        dctx = dict(ctx)
        dctx["halo"] = dtr.config.halo
        # shard_map body avals are block-local: scale-relative rules
        # compare against the PER-DEVICE activation footprint
        dctx["vf_elems"] = (_V * _F) // parts
        dctx["mesh_parts"] = parts
        units.append(JaxprUnit(
            "dist_train_step", jax.make_jaxpr(dtr._train_step._jit)(
                dtr.params, dtr.opt_state, d.feats, d.labels, d.mask,
                d.edge_src, d.edge_dst, d.in_degree, d.ell_idx,
                d.ell_row_pos, d.ell_row_id, d.ring_idx, d.sect_idx,
                d.sect_sub_dst, d.bd_tabs, fuse_tabs, key, lr),
            **dctx))
        units.append(JaxprUnit(
            "dist_eval_step", jax.make_jaxpr(dtr._eval_step._jit)(
                dtr.params, d.feats, d.labels, d.mask, d.edge_src,
                d.edge_dst, d.in_degree, d.ell_idx, d.ell_row_pos,
                d.ell_row_id, d.ring_idx, d.sect_idx, d.sect_sub_dst,
                d.bd_tabs, fuse_tabs),
            **dctx))

    findings = run_jaxpr_lint(units, select=select)

    if len(jax.devices()) > 1 and (select is None
                                   or "partition-imbalance" in select):
        # the split the distributed trainer ACTUALLY built on the rig
        findings.extend(check_partition_imbalance(
            "partition:dist_trainer", dtr.pg.real_edges,
            dtr.pg.num_parts))

    collective_selected = (select is None or any(
        s.startswith("collective-") for s in select))
    if len(jax.devices()) > 1 and collective_selected:
        from jax.sharding import PartitionSpec as P

        from ..parallel.distributed import PARTS_AXIS, _shard_map
        from ..parallel.ring import build_ring_tables, ring_aggregate
        axes = {PARTS_AXIS: parts}
        # the dist steps' traced collectives (gradient psum, halo
        # gather, metrics reduction) re-use the jaxprs above
        by_name = {u.name: u for u in units}
        cunits = [CollectiveUnit(n, by_name[n].jaxpr, axes)
                  for n in ("dist_train_step", "dist_eval_step")
                  if n in by_name]
        # the ring-halo subroutine, traced standalone: the gather-halo
        # trainer above never emits a ppermute, and the ring schedule
        # is exactly what the cycle rule exists to verify
        rt = build_ring_tables(dtr.pg)
        ring_fn = _shard_map(
            lambda x, s_, d_: ring_aggregate(
                x[0], s_[0], d_[0], axis_name=PARTS_AXIS),
            dtr.mesh, (P(PARTS_AXIS),) * 3, P(PARTS_AXIS))
        cunits.append(CollectiveUnit(
            "ring_halo", jax.make_jaxpr(ring_fn)(
                jnp.zeros((parts, dtr.pg.part_nodes, 8), jnp.float32),
                jnp.asarray(rt.src), jnp.asarray(rt.dst)), axes))
        findings.extend(run_collective_lint(cunits, select=select))
        if select is None or "collective-ring-halo" in select:
            # structural: the ring tables vs the plan's halo stats —
            # two independent derivations of the same exchange
            findings.extend(check_ring_halo(
                "collective:ring_tables", dtr.pg, rt))

    hlo_selected = (select is None
                    or any(s.startswith("hlo-") for s in select))
    if hlo_selected:
        from ..obs.compile_watch import cost_summary
        compiled = tr._train_step._jit.lower(
            tr.params, tr.opt_state, key, lr, tr.feats, tr.labels,
            tr.mask, tr.gctx).compile()
        if select is None or "hlo-large-copy" in select:
            findings.extend(check_large_copy(
                "hlo:train_step", compiled.as_text(),
                copy_min_elems=_V * _F))
        if select is None or "hlo-bytes-model" in select:
            findings.extend(check_bytes_model(
                "hlo:train_step",
                cost_summary(compiled).get("bytes_accessed"),
                tr._modeled_bytes, factor=hlo_factor))
    return findings


def _needs_programspace(select: Optional[List[str]]) -> bool:
    if select is None:
        return True
    return any(s in PROGRAMSPACE_RULES for s in select)


def _needs_sharding(select: Optional[List[str]]) -> bool:
    if select is None:
        return True
    return any(s in SHARDING_RULES for s in select)


def analyze(root: str, select: Optional[List[str]] = None,
            trace: bool = True,
            program_budget: Optional[Dict[str, int]] = None,
            replication_budget: Optional[Dict[str, int]] = None,
            extras: Optional[Dict[str, Any]] = None) -> List[Finding]:
    """AST lint over ``root`` plus (when ``trace`` and a trace rule is
    selected) the jaxpr/HLO/collective stage and the program-space
    and sharding auditors.  Every finding is also emitted as an
    ``analysis``-category event.

    ``program_budget`` / ``replication_budget`` are the ratcheted
    per-rig-config bounds for the compile-explosion and
    replication-budget rules; None loads them from ``root``'s
    ``scripts/lint_baseline.json``.  ``extras``, when a dict,
    receives the auditors' reports under ``'programspace'`` /
    ``'sharding'``."""
    t0 = time.perf_counter()
    baseline_path = None
    if program_budget is None or replication_budget is None:
        import os
        baseline_path = os.path.join(root, "scripts",
                                     "lint_baseline.json")
    findings = run_ast_lint(root, select=select)
    # level six: the concurrency/signal-safety auditor — pure AST
    # (no jax, no trace stage), so it runs under every selection that
    # names one of its rules, including `--select concurrency`
    if select is None or any(s in CONCURRENCY_RULES for s in select):
        findings.extend(audit_concurrency(root, select=select,
                                          extras=extras))
    # level eight: the protocol auditor & bounded model checker —
    # pure AST + pure-Python BFS, same millisecond class as level six
    if select is None or any(s in PROTOCOL_RULES for s in select):
        findings.extend(audit_protocol(root, select=select,
                                       extras=extras))
    if trace and _needs_trace(select):
        findings.extend(build_trace_findings(select=select))
    if trace and _needs_programspace(select):
        if program_budget is None:
            from .findings import load_program_budget
            program_budget = load_program_budget(baseline_path)
        findings.extend(audit_program_space(
            select=select, program_budget=program_budget,
            extras=extras))
    # level seven: the sharding & replication auditor — its own rig
    # builds (no compiles), like the program-space level
    if trace and _needs_sharding(select):
        if replication_budget is None:
            from .findings import load_budget
            replication_budget = load_budget(baseline_path,
                                             "replication_budget")
        findings.extend(audit_sharding(
            select=select, replication_budget=replication_budget,
            extras=extras))
    findings = dedupe(findings)
    for f in findings:
        emit("analysis", f.render(), console=False, rule=f.rule,
             unit=f.unit, line=f.line, fingerprint=f.fingerprint)
    emit("analysis",
         f"roc-lint: {len(findings)} finding(s) in "
         f"{time.perf_counter() - t0:.1f}s", console=False,
         count=len(findings),
         rules=sorted({f.rule for f in findings}))
    return findings

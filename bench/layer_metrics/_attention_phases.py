"""Device time inside the train step's attention ops, by phase: shared
by ``attn_softmax_ms`` and ``attn_gather_ms``.

An attention op splits its ``roc.agg.op<i>`` scope into three nested
phase scopes (``roc_tpu/obs/scopes.py``: ``roc.attn.scores`` — the
``s`` / ``t`` projections, their per-edge gather, LeakyReLU, the mask;
``roc.attn.stats`` — row max, ``exp``, denominator; ``roc.attn.gather``
— the feature gather, the weighted sum and the division).  The join is
``_step_scopes.py``'s: the train step's instruction -> ``op_name`` map
from the compiled program's own text, the intervals in which that
module ran, each operation's self time; here reduced per (op, phase,
direction) through ``scopes.parse_op_phase``, averaged over the chips,
per traced epoch.  XLA books a fusion to one of its members, so a
``where`` of the mask fused into the ``exp`` moves between ``scores``
and ``stats``: the two are reported as one metric.

One diagnostic line goes to standard output: ``{"attn_phases": {"rows":
[[op, phase, "fwd" | "bwd", ms_per_epoch, calls], ...], "unphased_ms"}}``
— ``unphased_ms`` is the time under an attention op's ``agg`` scope and
under no phase (the zero row appended to the gathered features).  A
program without phase scopes (a parent commit, a model without
attention) gives nothing to read: no line, no metric.
"""

import json


def measure(run):
    if "attn_phases" not in run.scratch:
        run.scratch["attn_phases"] = _measure(run)
    return run.scratch["attn_phases"]


def attribute(tr, inside, scopes, ops, epochs, step_scopes):
    """The reduction, on a ``harness.trace.Trace``: ``inside`` is
    ``_step_scopes.module_intervals``' result, ``scopes`` the
    instruction -> op_name map, ``ops`` the indices of the attention
    ops.  Milliseconds are per epoch and per chip."""
    from harness import trace
    from roc_tpu.obs.scopes import parse_op_name, parse_op_phase
    chips = max(len(tr.chips), 1)
    per_ms = 1e-6 / chips / max(epochs, 1)
    rows, unphased_ns = {}, 0
    for chip, chip_ops in tr.chips.items():
        spans = inside.get(chip, [])
        starts = [lo for lo, _ in spans]
        for op in chip_ops:
            if not step_scopes._inside(spans, starts, op):
                continue
            m = trace.HLO_TEXT.match(op.name)
            op_name = scopes.get(m.group(1) if m else op.name) or ""
            key = parse_op_phase(op_name)
            if key is None:
                cls = parse_op_name(op_name)
                if cls and cls[0] == "agg" and cls[1] in ops:
                    unphased_ns += op.self_ns
                continue
            row = rows.setdefault(key[1:], [0, 0])
            row[0] += op.self_ns
            row[1] += 1
    return {"rows": [[i, ph, way, ns * per_ms, calls // chips]
                     for (i, ph, way), (ns, calls) in sorted(rows.items())],
            "unphased_ms": unphased_ns * per_ms}


def _measure(run):
    try:
        from roc_tpu.obs.scopes import parse_op_phase  # noqa: F401
    except ImportError:                  # a program from before the phases
        return None
    ask = getattr(getattr(run.trainer, "_train_step", None),
                  "instruction_scopes", None)
    ops = {e["op"] for e in (run.scratch.get("resolved") or {}).get(
        "attention") or []}
    if (ask is None or not ops or run.trace is None
            or not run.trace_epochs or not run.scratch.get("xplane")):
        return None
    got = ask()
    if got is None:
        return None
    step_scopes = run.cell.module("layer_metrics", "_step_scopes")
    out = attribute(
        run.trace, step_scopes.module_intervals(run.scratch["xplane"],
                                                got["module"]),
        got["scopes"], ops, run.trace_epochs, step_scopes)

    def shown(v):
        return None if run.rehearsal else v

    print(json.dumps({"attn_phases": {
        "rows": [[i, ph, way, shown(ms), n]
                 for i, ph, way, ms, n in out["rows"]],
        "unphased_ms": shown(out["unphased_ms"])}}), flush=True)
    return out


def phase_ms(run, phases):
    """Per-epoch self time of the ``phases`` over every attention op,
    forward and backward; None when there is nothing to read."""
    got = measure(run)
    if got is None or not got["rows"]:
        return None
    return sum(ms for _, ph, _, ms, _ in got["rows"] if ph in phases)

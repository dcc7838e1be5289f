"""Device time inside the train step, by program scope: shared by
``step_agg_ms``, ``step_model_ms`` and ``step_unscoped_share``.

The device trace names an operation by its HLO instruction
(``%fusion.28 = ...``), never by the ``jax.named_scope`` it was traced
under.  The program keeps the other half: ``ObservedJit.
instruction_scopes()`` gives, from the compiled train step's own text,
``{instruction name: op_name}``, where an ``op_name`` such as
``jit(step)/transpose(jvp(roc.agg.op03))/while/body/...`` holds the
scope and, in JAX's own ``transpose(`` wrapper, the direction
(``roc_tpu/obs/scopes.py`` lists the six classes).  The join here:

* the map of ``run.trainer._train_step`` (both trainers name it so);
* from the trace file (``run.scratch["xplane"]``), per chip, the
  intervals in which that module executed: the events of the ``XLA
  Modules`` line named ``<module>(<fingerprint>)`` on an accelerator,
  the operations whose ``hlo_module`` stat is the module on the CPU
  backend.  Instruction names are unique within a module only, so the
  operations of the eager programs around the step are left out.  (The
  distributed trainer's eval step compiles to the same module name,
  ``jit_step``: a traced stretch that holds evals at ``parts`` > 1
  would mix them in, and ``unmatched_ms`` > 0 would show it.  No
  admitted cell traces so.);
* each remaining ``Op``'s instruction name (``%<name> = `` on the chip;
  on the CPU backend the event's name is the instruction's) -> its
  ``op_name`` -> (class, op index, direction);
* ``Op.self_ns`` (net of nested children: a ``while`` and its body are
  not counted twice) summed per (class, op index, direction), averaged
  over the chips as ``trace.top_ops`` does, per traced epoch.

XLA gives a fusion the metadata of one of the operations fused into it,
so an activation folded into an aggregation's epilogue is booked to the
aggregation: a boundary error of a few ms.

One diagnostic line goes to standard output before the result line:
``{"step_scopes": {"module", "map_from", "map_s", "text_bytes",
"step_ms", "outside_ms", "unmatched_ms", "rows": [[class, op, "fwd" |
"bwd", ms_per_epoch, calls], ...], "unscoped_top": [[kind, ms], ...]}}``
— ``step_ms`` is all self time inside the module, ``outside_ms`` what
ran on the chips outside it, ``unmatched_ms`` the part of the unscoped
time whose instruction the map does not hold at all.  A program without
``instruction_scopes`` (a parent commit) gives nothing to read: no line,
no metric.
"""

import bisect
import json
import time

MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"


def module_intervals(xplane_path, module):
    """``{chip: [(start_ns, end_ns), ...]}``, sorted: when ``module``
    ran on each chip."""
    from jax.profiler import ProfileData
    from harness import trace
    out = {}
    planes = list(ProfileData.from_file(xplane_path).planes)
    for plane in planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        spans = out.setdefault(int(m.group(2)), [])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                spans += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                          for e in line.events
                          if e.name.split("(")[0] == module]
    if not out:                          # the CPU backend: no device plane
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    st = trace._stats(e)
                    if st.get("hlo_module") == module and "hlo_op" in st:
                        out.setdefault(
                            int(st.get("device_ordinal", 0)), []).append(
                            (int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    return {chip: trace.union(spans) for chip, spans in out.items()}


def _inside(spans, starts, op):
    i = bisect.bisect_right(starts, op.start) - 1
    return i >= 0 and op.end <= spans[i][1]


def attribute(tr, inside, scopes, epochs):
    """The reduction, on a ``harness.trace.Trace``: ``inside`` is
    :func:`module_intervals`' result, ``scopes`` the instruction ->
    op_name map.  Milliseconds are per epoch and per chip."""
    from harness import trace
    from roc_tpu.obs.scopes import parse_op_name
    chips = max(len(tr.chips), 1)
    per_ms = 1e-6 / chips / max(epochs, 1)
    rows, unscoped = {}, {}
    step_ns = outside_ns = unmatched_ns = 0
    for chip, ops in tr.chips.items():
        spans = inside.get(chip, [])
        starts = [lo for lo, _ in spans]
        for op in ops:
            if not _inside(spans, starts, op):
                outside_ns += op.self_ns
                continue
            step_ns += op.self_ns
            m = trace.HLO_TEXT.match(op.name)
            op_name = scopes.get(m.group(1) if m else op.name)
            key = parse_op_name(op_name or "")
            if key is None:
                key = (UNSCOPED, None, "fwd")
                unscoped[op.kind] = unscoped.get(op.kind, 0) + op.self_ns
                if op_name is None:
                    unmatched_ns += op.self_ns
            row = rows.setdefault(key, [0, 0])
            row[0] += op.self_ns
            row[1] += 1
    by_class = {}
    for (cls, _, _), (ns, _) in rows.items():
        by_class[cls] = by_class.get(cls, 0.0) + ns * per_ms
    return {
        "step_ms": step_ns * per_ms, "outside_ms": outside_ns * per_ms,
        "unmatched_ms": unmatched_ns * per_ms, "by_class": by_class,
        "rows": [[cls, idx, way, ns * per_ms, calls // chips]
                 for (cls, idx, way), (ns, calls) in sorted(
                     rows.items(), key=lambda kv: (
                         kv[0][0], -1 if kv[0][1] is None else kv[0][1],
                         kv[0][2]))
                 if cls != UNSCOPED],
        "unscoped_top": [[kind, ns * per_ms] for kind, ns in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:5]]}


def measure(run):
    if "step_scopes" not in run.scratch:
        run.scratch["step_scopes"] = _measure(run)
    return run.scratch["step_scopes"]


def _measure(run):
    ask = getattr(getattr(run.trainer, "_train_step", None),
                  "instruction_scopes", None)
    if (ask is None or run.trace is None or not run.trace_epochs
            or not run.scratch.get("xplane")):
        return None
    t0 = time.perf_counter()
    got = ask()
    map_s = time.perf_counter() - t0
    if got is None:
        return None
    out = attribute(
        run.trace, module_intervals(run.scratch["xplane"], got["module"]),
        got["scopes"], run.trace_epochs)

    def shown(v):
        return None if run.rehearsal else v

    print(json.dumps({"step_scopes": {
        "module": got["module"], "map_from": got["map_from"],
        "map_s": shown(map_s), "text_bytes": got["text_bytes"],
        "step_ms": shown(out["step_ms"]),
        "outside_ms": shown(out["outside_ms"]),
        "unmatched_ms": shown(out["unmatched_ms"]),
        "rows": [[c, i, w, shown(ms), n] for c, i, w, ms, n in out["rows"]],
        "unscoped_top": [[k, shown(ms)] for k, ms in out["unscoped_top"]]}}),
          flush=True)
    return out


def class_ms(run, classes):
    """Per-epoch self time under the scope ``classes``; None when the
    program gives no map."""
    got = measure(run)
    if got is None:
        return None
    return sum(got["by_class"].get(c, 0.0) for c in classes)

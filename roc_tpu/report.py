"""Run-report CLI: summarize event/metrics JSONL artifacts into tables.

``python -m roc_tpu.report ev.jsonl [ev_p1.jsonl ...|'ev_p*.jsonl']
[--metrics m.jsonl [--metrics m2.jsonl ...]]``

Accepts MULTIPLE event files (repeat the positional, or pass a glob) —
a multi-process run writes one JSONL per process, and the report
merges them instead of silently assuming one stream (each record's
clock tuple ``host``/``proc`` identifies its stream; a "processes"
header shows what was merged).  For a merged *timeline* view of the
same artifacts use ``python -m roc_tpu.timeline``.

Renders, from the artifacts a run with ``--events``/``--metrics``
leaves behind:

- the run manifest (what code/hardware/config actually executed);
- compile cost per step function, with the modeled-vs-actual HBM
  delta (the planner-vs-residency check);
- per-phase spans (compile / train / eval / streamed sub-phases) as
  p50/p90;
- throughput (edges/sec, TFLOP/s, MFU when the chip's peak is known);
- stall heartbeats, grouped by stage — where a hung run spent its
  time.

This is a *reader*: it works on artifacts from a dead run (the JSONL
sinks flush per line) and never touches a backend — no
``jax.devices()``, no claim on a chip.  ``python -m roc_tpu.report``
does import the ``roc_tpu`` package (and thus jax) on the way in; on
a box without jax, run it as a plain script instead — this module
deliberately has no package-relative imports:
``python roc_tpu/report.py events.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                # a run killed mid-write leaves at most one torn tail
                # line; skip rather than refuse the whole artifact
                continue
    return out


def _fmt_bytes(n: Optional[float]) -> str:
    if n is None:
        return "?"
    if abs(n) >= 1 << 28:
        return f"{n / 1024**3:.2f}GiB"
    if abs(n) >= 1 << 17:
        return f"{n / 1024**2:.1f}MiB"
    return f"{n / 1024:.1f}KiB"


def _pct(values: List[float], q: float) -> float:
    vs = sorted(values)
    if not vs:
        return 0.0
    idx = min(len(vs) - 1, max(0, int(round(q * (len(vs) - 1)))))
    return vs[idx]


def _rows(title: str, header: List[str],
          rows: List[List[str]], out) -> None:
    print(f"\n== {title} ==", file=out)
    if not rows:
        print("  (none)", file=out)
        return
    widths = [max(len(h), *(len(r[i]) for r in rows))
              for i, h in enumerate(header)]
    print("  " + "  ".join(h.ljust(w) for h, w in zip(header, widths)),
          file=out)
    for r in rows:
        print("  " + "  ".join(c.ljust(w) for c, w in zip(r, widths)),
              file=out)


def _stream_key(rec: Dict[str, Any]):
    try:
        proc = int(rec.get("proc", 0) or 0)
    except (TypeError, ValueError):
        proc = 0
    return (str(rec.get("host", "?")), proc)


def summarize(events: List[Dict[str, Any]],
              metrics: Optional[List[Dict[str, Any]]] = None,
              out=None,
              concurrency: Optional[Dict[str, Any]] = None,
              protocol: Optional[Dict[str, Any]] = None) -> int:
    out = out if out is not None else sys.stdout

    # merged multi-process artifacts: one JSONL per process, each
    # record stamped with its (host, proc) clock identity — say what
    # was merged before aggregating across it
    streams: Dict[Any, int] = {}
    for e in events:
        k = _stream_key(e)
        streams[k] = streams.get(k, 0) + 1
    if len(streams) > 1:
        print("processes (merged event streams):", file=out)
        for (host, proc), n in sorted(streams.items(),
                                      key=lambda kv: kv[0][1]):
            print(f"  proc{proc}@{host}: {n} events", file=out)

    manifests = [e for e in events if e.get("cat") == "manifest"]
    if manifests:
        m = manifests[-1]
        res = m.get("resolved") or {}
        ds = m.get("dataset") or {}
        print("run manifest:", file=out)
        print(f"  platform={m.get('platform')} "
              f"devices={m.get('device_count')} "
              f"kinds={m.get('device_kinds')} "
              f"jax={m.get('jax_version')} "
              f"sha={(m.get('git_sha') or 'none')[:12]}", file=out)
        print(f"  dataset={ds.get('name')} V={ds.get('num_nodes')} "
              f"E={ds.get('num_edges')}", file=out)
        print("  resolved: " + " ".join(
            f"{k}={v}" for k, v in res.items()), file=out)
    else:
        print("run manifest: (none recorded)", file=out)

    decisions = [e for e in events
                 if e.get("cat") in ("resolve", "plan")]
    _rows("decisions (resolve/plan)", ["cat", "message"],
          [[e["cat"], str(e.get("msg", ""))[:96]] for e in decisions],
          out)

    compiles = [e for e in events
                if e.get("cat") == "compile" and "lower_s" in e]
    rows = []
    for e in compiles:
        modeled, peak = e.get("modeled_bytes"), e.get("peak_bytes")
        ratio = (f"{peak / modeled:.2f}x"
                 if peak is not None and modeled else "?")
        flops = e.get("flops")
        rows.append([
            str(e.get("name")),
            f"{e.get('lower_s', 0) + e.get('compile_s', 0):.2f}s",
            f"{flops:.3g}" if flops is not None else "?",
            _fmt_bytes(e.get("bytes_accessed")),
            _fmt_bytes(peak), _fmt_bytes(modeled), ratio])
    _rows("compile (XLA introspection)",
          ["step", "lower+compile", "flops", "bytes", "peak_hbm",
           "modeled", "actual/model"], rows, out)

    # compile-cache prewarm: per-config warm-vs-cold summaries
    # (utils/prewarm.py emits one summary event per warmed config)
    # — a repeat run should be all-warm, and cold counts on an
    # unchanged config mean program-set or cache-key drift
    pre = [e for e in events if e.get("cat") == "compile"
           and e.get("summary") and "prewarm" in e]
    rows = []
    for e in pre:
        rows.append([
            str(e.get("prewarm")), str(e.get("programs")),
            str(e.get("compile_warm_hits")),
            str(e.get("compile_cold")),
            str(e.get("failed", 0)),
            f"{float(e.get('prewarm_s', 0)):.1f}s"])
    _rows("compile cache (prewarm warm-vs-cold)",
          ["config", "programs", "warm_hits", "cold", "failed",
           "total"], rows, out)

    # phase spans: the trainer emits a final spans summary; fall back
    # to aggregating the per-eval epoch events / metrics records
    span_events = [e for e in events
                   if e.get("cat") == "epoch" and e.get("spans")]
    rows = []
    if span_events:
        for name, s in span_events[-1]["spans"].items():
            rows.append([name, str(s.get("n")),
                         f"{s.get('p50_ms', 0):.1f}",
                         f"{s.get('p90_ms', 0):.1f}",
                         f"{s.get('total_ms', 0):.0f}"])
    else:
        series: Dict[str, List[float]] = {}
        recs = [e for e in events if e.get("cat") == "epoch"]
        recs += metrics or []
        for e in recs:
            for k in ("epoch_ms", "eval_ms", "compile_ms"):
                if isinstance(e.get(k), (int, float)):
                    series.setdefault(k[:-3], []).append(float(e[k]))
        for name, vs in series.items():
            rows.append([name, str(len(vs)), f"{_pct(vs, 0.5):.1f}",
                         f"{_pct(vs, 0.9):.1f}", f"{sum(vs):.0f}"])
    _rows("phase spans (ms)",
          ["phase", "n", "p50", "p90", "total"], rows, out)

    thr: Dict[str, List[float]] = {}
    for e in ([x for x in events if x.get("cat") == "epoch"]
              + (metrics or [])):
        for k in ("edges_per_s", "tflops_per_s", "mfu"):
            if isinstance(e.get(k), (int, float)):
                thr.setdefault(k, []).append(float(e[k]))
    rows = [[k, f"{_pct(vs, 0.5):.4g}", f"{max(vs):.4g}"]
            for k, vs in thr.items()]
    _rows("throughput", ["metric", "p50", "max"], rows, out)

    # pipelined execution: overlap_frac = fraction of host->device
    # staging latency hidden under compute (1.0 = fully overlapped,
    # 0.0 = the synchronous prefetch=0 path); h2d_wait_p50_ms = the
    # un-hidden per-block stall.  Ring hop_compute/hop_permute rows
    # come from the micro_stream probe's pipeline events.
    pipe: Dict[str, List[float]] = {}
    for e in ([x for x in events
               if x.get("cat") in ("epoch", "pipeline")]
              + (metrics or [])):
        for k in ("overlap_frac", "h2d_wait_p50_ms",
                  "h2d_stage_p50_ms", "prefetch_depth",
                  "hop_compute_ms", "hop_permute_ms"):
            if isinstance(e.get(k), (int, float)):
                pipe.setdefault(k, []).append(float(e[k]))
    rows = [[k, f"{_pct(vs, 0.5):.4g}", f"{min(vs):.4g}",
             f"{max(vs):.4g}"] for k, vs in pipe.items()]
    _rows("pipeline (h2d prefetch / ring overlap)",
          ["metric", "p50", "min", "max"], rows, out)

    # partition load balance: the manifest's split-quality record
    # (per-part padded shapes + halo rows, the shapes that gate every
    # SPMD step) plus the cost-model event stream — every recorded
    # imbalance / repartition decision of the run
    part = (manifests[-1].get("partition") or {}) if manifests else {}
    rows = []
    if part.get("real_edges"):
        cols = [part.get(k) or [] for k in
                ("padded_edges", "padded_nodes", "halo_in",
                 "halo_out")]
        for p, re_ in enumerate(part["real_edges"][:16]):
            rows.append([str(p), str(re_)]
                        + [str(c[p]) if p < len(c) else "?"
                           for c in cols])
        if len(part["real_edges"]) > 16:
            rows.append(["...", "", "", "", "", ""])
    _rows("partition load balance",
          ["part", "real_edges", "padded_edges", "padded_nodes",
           "halo_in", "halo_out"], rows, out)
    if part:
        print(f"  imbalance max/mean: edges "
              f"{part.get('edge_imbalance')} nodes "
              f"{part.get('node_imbalance')}  (padded shard "
              f"{part.get('part_nodes')} nodes x "
              f"{part.get('part_edges')} edges)", file=out)
    cm = [e for e in events if e.get("cat") == "costmodel"
          and ("rebalance" in e or "gain" in e)]
    _rows("cost model (rebalance decisions)", ["message"],
          [[str(e.get("msg", ""))[:110]] for e in cm], out)

    # program space: the auditor's compile-budget reports (one event
    # per rig config, cat=programspace) — program count vs the
    # baselined bound, the static compile-wall tripwire
    ps = [e for e in events if e.get("cat") == "programspace"
          and "programs" in e]
    rows = []
    for e in ps:
        b, d = e.get("budget"), e.get("delta")
        rows.append([
            str(e.get("config")), str(e.get("programs")),
            str(e.get("observed_programs", "?")),
            f"{float(e.get('modeled_compile_ms', 0)) / 1e3:.1f}s",
            "?" if b is None else str(b),
            "?" if d is None else f"{d:+d}"])
    _rows("program space (compile budget)",
          ["config", "programs", "observed", "modeled_compile",
           "budget", "delta"], rows, out)

    # resilience: the fault-tolerance lifecycle (roc_tpu/resilience) —
    # injected drill faults, recovery retries, corrupt-checkpoint
    # fallbacks, preemptions/emergency checkpoints, elastic restores.
    # A clean run shows (none); every row here is either a drill or an
    # incident the run survived.
    res = [e for e in events if e.get("cat") == "resilience"]
    by_kind: Dict[str, List[Dict[str, Any]]] = {}
    for e in res:
        by_kind.setdefault(str(e.get("kind", "?")), []).append(e)
    rows = [[kind, str(len(es)), str(es[-1].get("msg", ""))[:84]]
            for kind, es in sorted(by_kind.items())]
    _rows("resilience (faults injected / recoveries)",
          ["kind", "n", "last"], rows, out)

    # concurrency surface: the level-six auditor's discovered thread
    # model — every thread, sync object, and signal handler per
    # module, so the table documents what runs concurrently with the
    # step loop.  Source: the ``--concurrency`` payload (the
    # ``python -m roc_tpu.analysis --select concurrency --json``
    # report scripts/test.sh writes), or the
    # ``concurrency_surface`` analysis event any audited run leaves
    # in its event stream.
    conc = concurrency
    if conc is None:
        evs = [e for e in events if e.get("cat") == "analysis"
               and e.get("kind") == "concurrency_surface"]
        if evs:
            conc = {"modules": evs[-1].get("modules") or [],
                    "totals": evs[-1].get("totals") or {}}
    rows = []
    for mod in (conc or {}).get("modules", []):
        threads = ", ".join(
            (str(t.get("target") or "?")
             + ("(daemon)" if t.get("daemon") else ""))
            for t in mod.get("threads", [])) or "-"
        locks = ", ".join(
            f"{lk.get('name')}[{lk.get('kind')}]"
            for lk in mod.get("locks", [])) or "-"
        handlers = ", ".join(str(h.get("handler") or "?")
                             for h in mod.get("handlers", [])) or "-"
        rows.append([str(mod.get("module", "?")), threads, locks,
                     handlers])
    _rows("concurrency surface (threads / sync objects / handlers)",
          ["module", "threads", "sync objects", "signal handlers"],
          rows, out)

    # protocol surface: the level-eight auditor's wire vocabulary +
    # model-check verdicts.  Source: the ``--protocol`` payload (the
    # ``python -m roc_tpu.analysis --select protocol --json`` report)
    # or the ``protocol_surface`` event any audited run leaves in its
    # stream.
    proto = protocol
    if proto is None:
        evs = [e for e in events
               if e.get("kind") == "protocol_surface"]
        if evs:
            proto = {"channels": evs[-1].get("channels") or [],
                     "models": evs[-1].get("models") or [],
                     "totals": evs[-1].get("totals") or {}}
    if proto:
        summarize_protocol(proto, out)

    # sharding: the level-seven auditor's replication ledger +
    # mesh-portability report (cat=sharding events, or the
    # --sharding payload below via summarize_sharding)
    sh = [e for e in events if e.get("cat") == "sharding"
          and "replicated_bytes" in e]
    if sh:
        summarize_sharding(
            [{**e, "ledger": e.get("ledger") or []} for e in sh],
            out)

    # SLO transitions: the burn-rate engine's dated breach/recovered
    # events (obs/slo.py) — every row is an objective crossing its
    # alert threshold (or coming back).  A clean run shows (none);
    # `--slo` renders the focused view of the same records plus the
    # live registry-snapshot dashboard.
    summarize_slo_events(events, out)

    stalls = [e for e in events if e.get("cat") == "stall"]
    by_stage: Dict[str, List[float]] = {}
    for e in stalls:
        by_stage.setdefault(str(e.get("stage")), []).append(
            float(e.get("elapsed_s", 0)))
    rows = [[st, str(len(vs)), f"{max(vs):.0f}s"]
            for st, vs in by_stage.items()]
    _rows("stalls (heartbeats)", ["stage", "beats", "max_wait"],
          rows, out)
    return 0


def summarize_sharding(reports: List[Dict[str, Any]],
                       out=None) -> int:
    """Render the sharding auditor's per-rig records: the
    replication-budget line, the mesh-portability per-device HBM at
    every (parts, model) shape, every full-width-materialization
    site with its modeled per-device bytes, and the top of the
    replication ledger.  Input: the ``sharding`` list of
    ``python -m roc_tpu.analysis --select sharding --json`` (or the
    equivalent ``sharding`` event records)."""
    out = out if out is not None else sys.stdout
    for rep in reports:
        cfg = rep.get("config", "?")
        b = rep.get("budget")
        d = rep.get("delta")
        shape = rep.get("canonical_shape") or ["?", "?"]
        print(f"\n== sharding {cfg} (parts={rep.get('parts')}) ==",
              file=out)
        print(f"  replicated/step on {shape[0]}x{shape[1]}: "
              f"{_fmt_bytes(rep.get('replicated_bytes'))}  "
              f"(budget "
              + ("unset — run --update-baseline" if b is None
                 else f"{_fmt_bytes(b)}, delta {d:+d} B") + ")",
              file=out)
        rows = []
        for m in rep.get("mesh_shapes") or []:
            reps_ = sorted({a for c in (m.get("components")
                                        or {}).values()
                            for a in c.get("replicated", [])})
            rows.append([f"{m.get('parts')}x{m.get('model')}",
                         _fmt_bytes(m.get("per_device_bytes")),
                         ",".join(reps_) or "-"])
        _rows(f"{cfg}: modeled per-device HBM by (parts x model)",
              ["mesh", "per_device", "replicated components"],
              rows, out)
        rows = []
        sites = rep.get("sites")
        if sites is None:
            sites = [s for slot in rep.get("slots") or []
                     for s in slot.get("sites") or []]
        for s in sites:
            per = s.get("per_device_bytes") or {}
            rows.append([
                str(s.get("op")), str(s.get("kind")),
                f"{s.get('dtype')}{s.get('shape')}",
                "/".join(s.get("lost") or []),
                str(s.get("layer")), str(s.get("src") or "-")]
                + [_fmt_bytes(per.get(k)) for k in
                   ("1x8", "2x4", "4x2")])
        _rows(f"{cfg}: full-width-materialization sites "
              f"(portability sim)",
              ["op", "kind", "tensor", "lost", "layer", "src",
               "dev@1x8", "dev@2x4", "dev@4x2"], rows, out)
        rows = []
        for e in (rep.get("ledger") or [])[:10]:
            rows.append([
                str(e.get("role")),
                f"{e.get('dtype')}{e.get('shape')}",
                _fmt_bytes(e.get("bytes")),
                ",".join(e.get("split") or []) or "-",
                ",".join(e.get("replicated") or []) or "-",
                _fmt_bytes(e.get("per_device_bytes"))])
        _rows(f"{cfg}: replication ledger (top 10, "
              f"{shape[0]}x{shape[1]})",
              ["role", "tensor", "bytes", "split", "replicated",
               "per_device"], rows, out)
    return 0


def summarize_protocol(surface: Dict[str, Any], out=None) -> int:
    """Render the level-eight protocol audit: the per-channel wire
    vocabulary (kind, field contract, send/handle sites, drift
    status), each dispatcher's unknown-kind-rejection verdict, the
    bounded model checker's per-model state counts and invariant
    verdicts (with counterexample schedules when a violation fired),
    and the lifecycle/commit transition-site index.  Input: the
    ``protocol_surface`` of ``python -m roc_tpu.analysis --select
    protocol --json`` (or the equivalent ``protocol`` event)."""
    out = out if out is not None else sys.stdout
    for chan in surface.get("channels") or []:
        rows = []
        for kind, k in sorted((chan.get("kinds") or {}).items()):
            sent_at = ",".join(str(x) for x in k.get("sent_at") or [])
            if not sent_at:
                sent_at = ("(by design)" if k.get("sent") is False
                           else "-")
            rows.append([
                kind,
                ",".join(k.get("required") or []) or "?",
                ",".join(k.get("optional") or []) or "-",
                sent_at,
                ",".join(str(x) for x in k.get("handled_at") or [])
                or "-",
                str(k.get("status", "?"))])
        _rows(f"wire vocabulary: {chan.get('name')} "
              f"({chan.get('sender')} -> {chan.get('receiver')})",
              ["kind", "required", "optional", "sent@", "handled@",
               "status"], rows, out)
        rej = ", ".join(
            f"{d.get('func')}:{d.get('line')}"
            + ("" if d.get("rejects_unknown") else " [NO REJECTION]")
            for d in chan.get("dispatchers") or []) or "(none)"
        print(f"  unknown-kind rejection: {rej}", file=out)
    rows = [[str(m.get("model", "?")), str(m.get("states")),
             str(m.get("transitions")),
             "yes" if m.get("complete") else "BUDGET EXHAUSTED",
             str(len(m.get("violations") or [])),
             ", ".join(m.get("invariants") or [])]
            for m in surface.get("models") or []]
    _rows("protocol models (bounded exhaustive exploration)",
          ["model", "states", "transitions", "complete",
           "violations", "invariants"], rows, out)
    for m in surface.get("models") or []:
        for v in m.get("violations") or []:
            print(f"  VIOLATION {m.get('model')}/"
                  f"{v.get('invariant')}: {v.get('msg')}", file=out)
            sched = " -> ".join(v.get("trace") or [])
            print(f"    schedule: {sched or '<initial state>'}",
                  file=out)
    rows = [[str(s.get("machine", "?")), str(s.get("module", "?")),
             str(s.get("site", "?")), str(s.get("line") or "-"),
             "yes" if s.get("present") else "MISSING"]
            for s in surface.get("sites") or []]
    _rows("protocol transition sites",
          ["machine", "module", "site", "line", "present"], rows, out)
    return 0


def summarize_slo_events(events: List[Dict[str, Any]],
                         out=None) -> int:
    """The dated SLO transition table: one row per burn-rate
    breach/recovered event (``cat=slo``), wall-clock stamped — the
    post-mortem's 'when did serving go out of objective, and when did
    it come back'."""
    import time as _time
    out = out if out is not None else sys.stdout
    rows = []
    for e in events:
        if e.get("cat") != "slo":
            continue
        t = e.get("t")
        when = (_time.strftime("%Y-%m-%d %H:%M:%S",
                               _time.localtime(float(t)))
                if t is not None else "?")
        rows.append([when, str(e.get("kind", "?")),
                     str(e.get("slo", "?")),
                     str(e.get("component", "?")),
                     f"{float(e.get('burn', 0)):.1f}x",
                     str(e.get("value")),
                     str(e.get("target")),
                     str(e.get("spec", ""))[:48]])
    _rows("slo transitions (burn-rate alerts)",
          ["when", "kind", "slo", "component", "burn", "value",
           "target", "spec"], rows, out)
    return 0


def summarize_slo(doc: Dict[str, Any], out=None) -> int:
    """Render one metrics-registry snapshot (the ``reg.dump`` /
    ``ROC_TPU_SLO_SNAPSHOT`` artifact) as the live text dashboard:
    the SLO verdict first (health + per-objective burn/value), then
    every counter/gauge/histogram with its windowed view.  Pairs with
    ``watch``: ``watch -n1 python -m roc_tpu.report --slo snap.json``
    is the fleet console."""
    out = out if out is not None else sys.stdout
    windows = [int(w) for w in doc.get("windows_s") or []]
    print(f"slo dashboard: registry '{doc.get('registry', '?')}'"
          + (f"  component={doc['component']}"
             if doc.get("component") else "")
          + (f"  t={doc['t']}" if doc.get("t") is not None else ""),
          file=out)
    health = doc.get("health")
    if health is not None:
        verdict = "OK" if health.get("ok") else "BREACH"
        line = f"  health: {verdict}"
        if health.get("replicas") is not None:
            line += (f"  ({health.get('replicas_alive', '?')}/"
                     f"{health['replicas']} replicas alive)")
        print(line, file=out)
        rows = []
        for ob in health.get("objectives") or []:
            state = (health.get("states") or {}).get(
                ob.get("name"), "?")
            rows.append([str(ob.get("name")),
                         str(ob.get("spec", ""))[:52],
                         state,
                         "yes" if ob.get("compliant") else "NO",
                         str(ob.get("value")),
                         str(ob.get("target")),
                         f"{float(ob.get('burn', 0)):.2f}x",
                         f"{float(ob.get('bad_frac', 0)):.4f}",
                         f"{float(ob.get('budget', 0)):.4f}"])
        _rows("objectives",
              ["name", "spec", "state", "compliant", "value",
               "target", "burn", "bad_frac", "budget"], rows, out)
    metrics = doc.get("metrics") or {}
    rows = []
    for name in sorted(metrics):
        m = metrics[name]
        if m.get("kind") == "counter":
            rows.append([name, str(m.get("total"))]
                        + [str(m.get(f"sum_{w}s", "?"))
                           for w in windows])
    _rows("counters", ["name", "total"]
          + [f"sum_{w}s" for w in windows], rows, out)
    rows = []
    for name in sorted(metrics):
        m = metrics[name]
        if m.get("kind") == "gauge":
            rows.append([name, str(m.get("value")),
                         str(m.get("ewma", "-")), str(m.get("n"))])
    _rows("gauges", ["name", "value", "ewma", "n"], rows, out)
    rows = []
    for name in sorted(metrics):
        m = metrics[name]
        if m.get("kind") == "histogram":
            row = [name, str(m.get("total")), str(m.get("mean"))]
            for w in windows:
                row += [str(m.get(f"n_{w}s", "?")),
                        str(m.get(f"p50_{w}s")),
                        str(m.get(f"p99_{w}s"))]
            rows.append(row)
    hdr = ["name", "total", "mean"]
    for w in windows:
        hdr += [f"n_{w}s", f"p50_{w}s", f"p99_{w}s"]
    _rows("histograms (ms)", hdr, rows, out)
    return 0


def _expand(patterns: List[str]) -> List[str]:
    """Literal paths plus glob patterns, deduped, order-preserving;
    a missing path / zero-match glob is KEPT so the open() below
    fails loudly.  Duplicated from obs/timeline.py expand_paths on
    purpose: this module deliberately has no package-relative imports
    (plain-script mode on boxes without jax, see module docstring) —
    keep the two behaviors in lockstep."""
    import glob as _glob
    import os
    out: List[str] = []
    for p in patterns:
        hits = [p] if os.path.exists(p) else sorted(_glob.glob(p))
        for h in (hits or [p]):
            if h not in out:
                out.append(h)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="roc_tpu.report", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("events", nargs="*",
                    help="event-log JSONL file(s) (--events / "
                         "ROC_TPU_EVENTS artifacts; repeat or glob "
                         "for multi-process runs — one file per "
                         "process).  Optional with --sharding, which "
                         "can render without a run artifact")
    ap.add_argument("--metrics", action="append", default=None,
                    help="training metrics JSONL (--metrics artifact) "
                         "to fold into the span/throughput tables; "
                         "repeatable for multi-process runs")
    ap.add_argument("--concurrency", default=None,
                    help="`python -m roc_tpu.analysis --select "
                         "concurrency --json` payload: renders the "
                         "concurrency-surface table (threads / locks "
                         "/ signal handlers per module) from it "
                         "instead of the event stream")
    ap.add_argument("--protocol", default=None, metavar="FILE",
                    help="`python -m roc_tpu.analysis --select "
                         "protocol --json` payload: renders the "
                         "level-eight wire-vocabulary, model-check "
                         "and transition-site tables from it (works "
                         "with or without event files)")
    ap.add_argument("--sharding", nargs="?", const="__live__",
                    default=None, metavar="FILE",
                    help="render the sharding auditor's replication "
                         "ledger + mesh-portability report.  With "
                         "FILE: a `python -m roc_tpu.analysis "
                         "--select sharding --json` payload.  "
                         "Without FILE (and no event files): run "
                         "the audit live on the 8-virtual-device "
                         "CPU rig — the one mode of this tool that "
                         "imports jax")
    ap.add_argument("--slo", nargs="?", const="__events__",
                    default=None, metavar="SNAPSHOT",
                    help="SLO/observability view.  With SNAPSHOT: "
                         "render a metrics-registry snapshot JSON "
                         "(the Router's ROC_TPU_SLO_SNAPSHOT / "
                         "MetricsRegistry.dump artifact) as the live "
                         "dashboard — watch-able: `watch -n1 python "
                         "-m roc_tpu.report --slo snap.json`.  "
                         "Without SNAPSHOT (bare --slo) with event "
                         "files: render only the dated SLO "
                         "transition table from the event stream")
    args = ap.parse_args(argv)
    # --slo SNAPSHOT: the registry-snapshot dashboard; renders with
    # or without event files (with them, the focused transition table
    # from the events follows)
    if args.slo is not None and args.slo != "__events__":
        try:
            with open(args.slo) as f:
                snap = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read {args.slo}: {e}",
                  file=sys.stderr)
            return 2
        summarize_slo(snap if isinstance(snap, dict) else {})
        if not args.events:
            return 0
        events = []
        for path in _expand(args.events):
            try:
                events.extend(load_jsonl(path))
            except OSError as e:
                print(f"error: cannot read {path}: {e}",
                      file=sys.stderr)
                return 2
        events.sort(key=lambda e: float(e.get("t") or 0.0))
        return summarize_slo_events(events)
    if args.slo == "__events__":
        if not args.events:
            ap.error("--slo without a SNAPSHOT file needs event "
                     "files to read transitions from")
        events = []
        for path in _expand(args.events):
            try:
                events.extend(load_jsonl(path))
            except OSError as e:
                print(f"error: cannot read {path}: {e}",
                      file=sys.stderr)
                return 2
        events.sort(key=lambda e: float(e.get("t") or 0.0))
        return summarize_slo_events(events)
    # --sharding FILE loads the payload up front, whether or not
    # event files are also given — an explicitly-passed report must
    # render either way (with events, its tables follow the event
    # summary)
    sharding_reports: Optional[List[Dict[str, Any]]] = None
    if args.sharding is not None and args.sharding != "__live__":
        try:
            with open(args.sharding) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read {args.sharding}: {e}",
                  file=sys.stderr)
            return 2
        reports = (payload.get("sharding", payload)
                   if isinstance(payload, dict) else payload)
        sharding_reports = reports if isinstance(reports, list) \
            else []
    # --protocol FILE: same contract — accepts the full --json
    # object or a bare protocol_surface dict; renders standalone
    # when no event files are given
    protocol: Optional[Dict[str, Any]] = None
    if args.protocol:
        try:
            with open(args.protocol) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read {args.protocol}: {e}",
                  file=sys.stderr)
            return 2
        surface = payload.get("protocol_surface", payload) \
            if isinstance(payload, dict) else None
        protocol = surface if isinstance(surface, dict) else None
    if not args.events:
        if args.sharding == "__live__":
            # live audit: the single backend-touching mode, kept out
            # of every artifact-reading path (module docstring) —
            # forced onto the CPU rig exactly like the analysis CLI
            from roc_tpu.analysis import force_cpu_rig
            force_cpu_rig()
            from roc_tpu.analysis.findings import load_budget
            from roc_tpu.analysis.sharding_lint import audit_sharding
            import os
            base = (os.getcwd() if os.path.isdir(
                os.path.join(os.getcwd(), "roc_tpu"))
                else os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
            budget = load_budget(
                os.path.join(base, "scripts", "lint_baseline.json"),
                "replication_budget")
            extras: Dict[str, Any] = {}
            audit_sharding(replication_budget=budget, extras=extras)
            return summarize_sharding(extras.get("sharding", []))
        rc = None
        if protocol is not None:
            rc = summarize_protocol(protocol)
        if sharding_reports is not None:
            rc = summarize_sharding(sharding_reports)
        if rc is not None:
            return rc
        ap.error("event files required (or --sharding / --protocol)")
    events: List[Dict[str, Any]] = []
    for path in _expand(args.events):
        try:
            events.extend(load_jsonl(path))
        except OSError as e:
            print(f"error: cannot read {path}: {e}", file=sys.stderr)
            return 2
    # merged streams interleave by wall clock so "last manifest" and
    # span ordering stay meaningful (stable: unstamped records keep
    # their file order)
    events.sort(key=lambda e: float(e.get("t") or 0.0))
    metrics = None
    if args.metrics:
        metrics = []
        for path in _expand(args.metrics):
            try:
                metrics.extend(load_jsonl(path))
            except OSError as e:
                print(f"error: cannot read {path}: {e}",
                      file=sys.stderr)
                return 2
    concurrency = None
    if args.concurrency:
        try:
            with open(args.concurrency) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read {args.concurrency}: {e}",
                  file=sys.stderr)
            return 2
        # accept the full --json object or a bare surface dict
        concurrency = payload.get("concurrency_surface", payload) \
            if isinstance(payload, dict) else None
    rc = summarize(events, metrics, concurrency=concurrency,
                   protocol=protocol)
    if sharding_reports is not None:
        summarize_sharding(sharding_reports)
    return rc


if __name__ == "__main__":
    sys.exit(main())

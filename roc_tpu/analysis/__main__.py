"""``python -m roc_tpu.analysis`` — the roc-lint CLI.

Exit code 0 means the tree is clean modulo the baseline; any
unbaselined finding exits 1 (lint semantics — this IS the gate the
tier runs).  Stdout is the product: one ``unit:line: [rule] message``
line per finding, then a summary.

Usage:
    python -m roc_tpu.analysis [--strict]          # full run
    python -m roc_tpu.analysis --select stdout-print   # one rule
    python -m roc_tpu.analysis --select concurrency    # level six
    python -m roc_tpu.analysis --select sharding       # level seven
    python -m roc_tpu.analysis --select protocol       # level eight
    python -m roc_tpu.analysis --update-baseline   # shrink ratchet
    python -m roc_tpu.analysis --json              # machine-readable

``--json`` prints one JSON object on stdout — findings, baseline
split, and the program-space compile-budget reports with full
program-key sets — so CI can diff program counts
across commits without parsing text.

The baseline (``scripts/lint_baseline.json``) is ratchet-only:
``--update-baseline`` rewrites it as the INTERSECTION of its current
entries and the findings that still fire — it can only shrink.  New
findings are fixed at the source or suppressed with an explanatory
``# roc-lint: ok=<rule>`` pragma, never absorbed.  ``--strict``
additionally fails on stale baseline entries, forcing the shrink to
be committed.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional


def _default_root() -> str:
    """Prefer CWD when it holds a roc_tpu/ tree (the thin-wrapper
    scripts cd to the repo they lint), else the checkout this module
    was imported from."""
    if os.path.isdir(os.path.join(os.getcwd(), "roc_tpu")):
        return os.getcwd()
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m roc_tpu.analysis",
        description="roc-lint: jaxpr/HLO/AST static analysis, "
                    "ratcheted via scripts/lint_baseline.json")
    p.add_argument("--root", default=None,
                   help="repo root to lint (default: cwd when it has "
                        "a roc_tpu/ tree, else this checkout)")
    p.add_argument("--select", default=None,
                   help="comma-separated rule names (default: all); "
                        "an AST-only selection skips the jax trace "
                        "stage entirely.  'concurrency' expands to "
                        "every level-six concurrency/signal-safety "
                        "rule (jax-free — the scripts/test.sh "
                        "preflight selection); "
                        "'sharding' expands to every level-seven "
                        "sharding/replication rule (runs the rig "
                        "builds + jaxpr walks, no compiles); "
                        "'protocol' expands to every level-eight "
                        "protocol-audit/model-check rule (jax-free "
                        "— preflight class)")
    p.add_argument("--no-trace", action="store_true",
                   help="skip the jaxpr/HLO trace stage (AST only)")
    p.add_argument("--baseline", default=None,
                   help="baseline path (default: "
                        "<root>/scripts/lint_baseline.json)")
    p.add_argument("--update-baseline", action="store_true",
                   help="shrink-only rewrite of the baseline "
                        "(drops entries that no longer fire)")
    p.add_argument("--strict", action="store_true",
                   help="also fail on stale baseline entries "
                        "(ratchet shrink must be committed)")
    p.add_argument("--list-rules", action="store_true",
                   help="print rule names and exit")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output: one JSON object "
                        "(findings + program-key sets) on stdout")
    args = p.parse_args(argv)

    select = ([s.strip() for s in args.select.split(",") if s.strip()]
              if args.select else None)
    if select:
        # group aliases: 'concurrency' names the whole level-six rule
        # set (expanded BEFORE the trace gating below so a
        # concurrency-only preflight never touches or forces jax);
        # 'sharding' names the level-seven set the same way
        from .concurrency_lint import CONCURRENCY_RULES
        from .protocol_lint import PROTOCOL_RULES
        from .sharding_lint import SHARDING_RULES
        groups = {"concurrency": CONCURRENCY_RULES,
                  "sharding": SHARDING_RULES,
                  "protocol": PROTOCOL_RULES}
        select = [r for s in select
                  for r in groups.get(s, (s,))]
    trace = not args.no_trace
    from .driver import is_trace_rule
    if trace and (select is None
                  or any(is_trace_rule(s) for s in select)):
        # the trace stage runs on the 8-virtual-device CPU rig,
        # unconditionally: the baseline fingerprints are CPU-rig
        # artifacts, and a TPU-host invocation must not spend chip
        # time (or drift the HLO) on a lint pass
        from . import force_cpu_rig
        force_cpu_rig()

    from .driver import all_rule_names, analyze
    from .findings import (load_baseline, shrink_baseline,
                           split_findings)

    if args.list_rules:
        for name in all_rule_names():
            print(name)
        return 0
    if select:
        known = set(all_rule_names())
        bad = sorted(set(select) - known)
        if bad:
            print(f"unknown rule(s): {', '.join(bad)}; see "
                  f"--list-rules")
            return 2

    root = args.root or _default_root()
    baseline_path = args.baseline or os.path.join(
        root, "scripts", "lint_baseline.json")
    extras: dict = {}
    from .findings import load_budget, load_program_budget
    findings = analyze(root, select=select, trace=trace,
                       program_budget=load_program_budget(
                           baseline_path),
                       replication_budget=load_budget(
                           baseline_path, "replication_budget"),
                       extras=extras)
    reports = extras.get("programspace", [])
    sh_reports = extras.get("sharding", [])
    # stale-entry accounting and the shrink ratchet are scoped to the
    # rules that actually ran: an AST-only / --select run must not
    # declare trace-rule baseline entries "no longer firing"
    active = set(select) if select else set(all_rule_names())
    if not trace:
        active = {r for r in active if not is_trace_rule(r)}
    # the two numeric ratchet TRACKS (findings.BUDGET_SECTIONS) share
    # one set of semantics — bound over measurement = finding (the
    # auditor emits it), measurement below bound = slack, missing
    # bound = tripwire disarmed, bound for a config that no longer
    # exists = orphan; slack/orphans/unbounded all fail --strict
    # until --update-baseline commits the shrink — so they are
    # processed by ONE loop over track descriptors
    from .driver import _needs_programspace, _needs_sharding
    ps_ran = trace and _needs_programspace(select)
    sh_ran = trace and _needs_sharding(select)
    tracks = [
        {"section": "program_budget", "label": "program budget",
         "ran": ps_ran, "reports": reports,
         "measured_key": "programs", "noun": "count",
         "guards": "the compile-explosion bound no longer guards "
                   "anything; "},
        {"section": "replication_budget",
         "label": "replication budget",
         "ran": sh_ran, "reports": sh_reports,
         "measured_key": "replicated_bytes", "noun": "bytes",
         "guards": ""},
    ]
    rig_names: set = set()
    if any(t["ran"] for t in tracks):
        from .programspace import rig_configs
        rig_names = set(rig_configs())

    def _orphans(track) -> List[str]:
        # bounds for rig configs that no longer EXIST (renamed or
        # removed — not merely unhosted on this box, whose bound is
        # deliberately kept) would otherwise disarm the tripwire
        # silently: the renamed config restarts at budget=None
        if not track["ran"]:
            return []
        return sorted(set(load_budget(baseline_path,
                                      track["section"]))
                      - rig_names)

    for t in tracks:
        t["orphans"] = _orphans(t)
    baseline = load_baseline(baseline_path)
    new, old, stale = split_findings(findings, baseline,
                                     active_rules=active)
    dropped = 0
    if args.update_baseline:
        # shrink FIRST (findings AND budgets), then re-split against
        # the updated file: all output below must describe the state
        # this run LEAVES, not the entries it just removed — a CI
        # consumer would otherwise re-flag a ratchet the same
        # invocation already cleared, and a first-ever run would
        # print bounds instructing the user to run the flag they are
        # running
        from .findings import shrink_budget
        kept = shrink_baseline(baseline_path, findings,
                               active_rules=active)
        dropped = len(baseline) - len(kept)
        for t in tracks:
            if not t["ran"]:
                continue
            budget = shrink_budget(
                baseline_path, t["section"],
                {r["config"]: r[t["measured_key"]]
                 for r in t["reports"]},
                known=rig_names)
            for rep in t["reports"]:
                b = budget.get(rep["config"])
                rep["budget"] = b
                if b is not None:
                    rep["delta"] = rep[t["measured_key"]] - b
            t["orphans"] = _orphans(t)
        baseline = load_baseline(baseline_path)
        new, old, stale = split_findings(findings, baseline,
                                         active_rules=active)
    # budget slack — same ratchet semantics as stale findings: a
    # measurement BELOW the recorded bound must be committed via
    # --update-baseline, or a later regression would hide inside the
    # slack and the tripwire would never fire.  A measured config
    # with NO bound at all is the limiting case of slack (infinite
    # headroom — the tripwire is disarmed for it), so under --strict
    # it fails the same way until --update-baseline initializes.
    for t in tracks:
        t["slack"] = [r for r in t["reports"]
                      if r.get("delta") is not None
                      and r["delta"] < 0]
        t["unbounded"] = [r for r in t["reports"]
                          if r.get("budget") is None]
    any_ratchet_debt = bool(stale) or any(
        t["slack"] or t["orphans"] or t["unbounded"] for t in tracks)
    prog, repl = tracks

    if args.json:
        import json as _json
        payload = {
            "findings": [
                {"rule": f.rule, "unit": f.unit, "line": f.line,
                 "msg": f.msg, "fingerprint": f.fingerprint,
                 "baselined": f.fingerprint in baseline,
                 "detail": f.detail}
                for f in new + old],
            "stale": sorted(stale),
            "budget_stale": prog["orphans"],
            "program_space": reports,
            "sharding": sh_reports,
            "replication_budget_stale": repl["orphans"],
            "concurrency_surface": extras.get("concurrency"),
            "protocol_surface": extras.get("protocol"),
            "summary": {"new": len(new), "baselined": len(old),
                        "stale": len(stale),
                        "budget_slack": len(prog["slack"]),
                        "budget_stale": len(prog["orphans"]),
                        "budget_unbounded": len(prog["unbounded"]),
                        "replication_slack": len(repl["slack"]),
                        "replication_stale": len(repl["orphans"]),
                        "replication_unbounded":
                            len(repl["unbounded"])},
        }
        print(_json.dumps(payload, indent=2))
        return (1 if new or (any_ratchet_debt and args.strict)
                else 0)

    for f in new:
        print(f.render())
    for f in old:
        print(f"{f.render()}  [baselined]")
    # the program-space compile budget — the static compile-wall
    # tripwire.  scripts/test.sh's pre-flight surfaces these lines, so
    # a PR that adds a compiled-program shape shows its delta before
    # the test tier even starts (red when it grew and a tty is
    # watching).
    for rep in reports:
        b = rep.get("budget")
        delta = rep.get("delta")
        d_txt = ("no baseline — run --update-baseline" if b is None
                 else f"baseline {b}, delta {delta:+d}")
        line = (f"program budget {rep['config']}: "
                f"{rep['programs']} programs, modeled compile "
                f"{rep['modeled_compile_ms'] / 1e3:.1f}s ({d_txt})")
        if delta is not None and delta > 0 and sys.stdout.isatty():
            line = f"\x1b[31m{line}\x1b[0m"
        print(line)
    # the sharding auditor's replication budget — the 2-D-mesh
    # tripwire: replicated bytes/step on the canonical candidate
    # mesh, ratcheted exactly like the program counts above
    for rep in sh_reports:
        b = rep.get("budget")
        delta = rep.get("delta")
        d_txt = ("no baseline — run --update-baseline" if b is None
                 else f"baseline {b}, delta {delta:+d}")
        line = (f"replication budget {rep['config']}: "
                f"{rep['replicated_bytes']} replicated B/step on "
                f"{rep['canonical_shape'][0]}x"
                f"{rep['canonical_shape'][1]}, "
                f"{rep['full_width_sites']} full-width site(s) "
                f"({d_txt})")
        if delta is not None and delta > 0 and sys.stdout.isatty():
            line = f"\x1b[31m{line}\x1b[0m"
        print(line)
    if args.update_baseline:
        print(f"baseline: kept {len(baseline)}, dropped {dropped} "
              f"stale entr{'y' if dropped == 1 else 'ies'} "
              f"({baseline_path})")
    else:
        if stale:
            verb = "FAIL" if args.strict else "note"
            print(f"{verb}: {len(stale)} stale baseline entr"
                  f"{'y' if len(stale) == 1 else 'ies'} no longer "
                  f"fire(s) — run --update-baseline to ratchet down:")
            for fp in sorted(stale):
                print(f"  {fp}")
        for t in tracks:
            verb = "FAIL" if args.strict else "note"
            if t["slack"]:
                print(f"{verb}: {len(t['slack'])} {t['label']}(s) "
                      f"above the measured {t['noun']} — run "
                      f"--update-baseline to ratchet down:")
                for rep in t["slack"]:
                    print(f"  {rep['config']}: "
                          f"{rep[t['measured_key']]} measured < "
                          f"{rep['budget']} baselined")
            if t["orphans"]:
                print(f"{verb}: {len(t['orphans'])} {t['label']} "
                      f"entr{'y' if len(t['orphans']) == 1 else 'ies'}"
                      f" for unknown rig config(s) — {t['guards']}run "
                      f"--update-baseline to drop:")
                for cfg in t["orphans"]:
                    print(f"  {cfg}")
            if t["unbounded"] and args.strict:
                print(f"FAIL: {len(t['unbounded'])} measured "
                      f"config(s) have no {t['section']} bound "
                      f"(tripwire disarmed) — run --update-baseline "
                      f"to initialize:")
                for rep in t["unbounded"]:
                    print(f"  {rep['config']}: "
                          f"{rep[t['measured_key']]} measured")

    print(f"roc-lint: {len(new)} new, {len(old)} baselined, "
          f"{len(stale)} stale")
    if new:
        return 1
    if any_ratchet_debt and args.strict:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

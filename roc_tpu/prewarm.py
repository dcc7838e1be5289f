"""``python -m roc_tpu.prewarm`` — pre-pay the compile wall.

Feeds the program-space auditor's exact static enumeration
(``analysis/programspace.py`` — keyed by the quantized plan shapes the
rebalancer preserves) into AOT ``lower().compile()`` against the
persistent compile cache, so rebalance / resume / serving all start
warm.  Compile-only: nothing executes on a device.

Usage:
    python -m roc_tpu.prewarm --cpu                # every rig, CPU
    python -m roc_tpu.prewarm --config sgc_stream  # one rig
    python -m roc_tpu.prewarm --cpu --jobs 2       # parallel procs

Writes the warm-state artifact (``benchmarks/programspace_warm.json``)
recording each warmed config's program-key set, diffable against
``python -m roc_tpu.analysis --json``.  Stdout gets one JSON line per
warmed config (machine-readable; `# ...` diagnostics go to stderr).
A config the backend cannot host (fewer devices than its mesh) is an
error: the command warms what it can and exits 1.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from typing import List, Optional


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="python -m roc_tpu.prewarm", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", default="all",
                    help="rig config name (analysis/programspace.py "
                         "rig_configs) or 'all' (default)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent cache directory (default: "
                         "$JAX_COMPILATION_CACHE_DIR, which also wins "
                         "over this flag, else <repo>/.jax_cache)")
    ap.add_argument("--state", default=None,
                    help="warm-state artifact path (default: "
                         "benchmarks/programspace_warm.json, honoring "
                         "ROC_TPU_BENCH_ARTIFACTS)")
    ap.add_argument("--no-state", action="store_true",
                    help="do not write the warm-state artifact")
    ap.add_argument("--jobs", type=int, default=1,
                    help="warm configs in N parallel child processes; "
                         "needs --cpu (an accelerator belongs to one "
                         "process, so a second child could never "
                         "reach it).  Concurrent children sharing one "
                         "cache dir make the warm-vs-cold attribution "
                         "best-effort (a sibling's write inside a "
                         "candidate's before/after window counts as "
                         "cold); the warm-state KEY sets stay exact")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (CI / cache priming "
                         "for CPU-rig tests)")
    ap.add_argument("-v", "--verbose", action="store_true")
    return ap.parse_args(argv)


def _parallel(names: List[str], args) -> int:
    """One child process per config, ``--jobs`` at a time.  Children
    print their JSON report line; the parent relays it and merges the
    warm state (children run --no-state so the artifact is written
    once, by the parent)."""
    base = [sys.executable, "-m", "roc_tpu.prewarm", "--no-state",
            "--jobs", "1"]
    for flag, val in (("--cache-dir", args.cache_dir),):
        if val:
            base += [flag, val]
    if args.cpu:
        base.append("--cpu")
    if args.verbose:
        base.append("-v")
    reports, rc = [], 0
    pending = list(names)
    running: List = []
    while pending or running:
        while pending and len(running) < max(1, args.jobs):
            name = pending.pop(0)
            running.append((name, subprocess.Popen(
                base + ["--config", name], stdout=subprocess.PIPE,
                stderr=sys.stderr, text=True)))
        name, proc = running.pop(0)
        out, _ = proc.communicate()
        for line in out.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    reports.append(json.loads(line))
                except ValueError:
                    pass
            if line:
                print(line)
        if proc.returncode != 0:
            print(f"# prewarm child {name} exited "
                  f"{proc.returncode}", file=sys.stderr)
            rc = 1
    if reports and not args.no_state:
        from .utils.prewarm import write_warm_state
        # keep keys=[] reports: an all-failed config must be RECORDED
        # as warmed-nothing, so a diff against the analysis report
        # shows its whole program set as growth (same semantics as
        # the sequential path)
        path = write_warm_state(
            [r for r in reports if "config" in r], args.state)
        print(f"# warm state -> {path}", file=sys.stderr)
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.jobs > 1 and not args.cpu:
        print("error: --jobs > 1 needs --cpu: an accelerator belongs "
              "to one process, so parallel children could never all "
              "reach it", file=sys.stderr)
        return 2
    if args.cpu:
        # before any backend init; children inherit the env too.  The
        # 8-virtual-device flag must land before CPU-client init or
        # the multi-device rigs (gin_flat8 parts=2) cannot be hosted
        from .analysis import force_cpu_rig
        force_cpu_rig()
    from .analysis.programspace import rig_configs
    names = (sorted(rig_configs()) if args.config == "all"
             else [args.config])
    unknown = [n for n in names if n not in rig_configs()]
    if unknown:
        print(f"error: unknown config(s) {unknown}; known: "
              f"{sorted(rig_configs())}", file=sys.stderr)
        return 2
    if args.jobs > 1 and len(names) > 1:
        return _parallel(names, args)

    from .utils.prewarm import prewarm_config, write_warm_state
    reports, rc = [], 0
    for name in names:
        try:
            rep = prewarm_config(name, cache_dir=args.cache_dir,
                                 verbose=args.verbose)
        except ValueError as e:     # the backend cannot host the rig
            print(f"error: {e}", file=sys.stderr)
            rc = 1
            continue
        reports.append(rep)
        print(json.dumps({k: v for k, v in rep.items()
                          if k != "slots"}))
    if reports and not args.no_state:
        path = write_warm_state(reports, args.state)
        print(f"# warm state -> {path}", file=sys.stderr)
    # a failed candidate was NOT warmed (its key is withheld): surface
    # it in the exit code
    if any(r.get("failed") for r in reports):
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())

"""Compile-cache pre-warm: pay the compile wall once, off the timed
path.

The program-space auditor (``analysis/programspace.py``) statically
enumerates the EXACT compiled-program set of a config — the same
``candidate_programs`` extraction here drives each candidate through
the AOT path (``jit.lower(*args).compile()``) against the persistent
compile cache (``utils/compile_cache.py``), so every later process
that builds the same trainer starts warm: rebalance, resume and
serving all skip the first-compile stall.  Compile-only — nothing
executes on the device.

Warm-vs-cold accounting is file-based: a candidate whose AOT compile
leaves NO new entry in the cache directory was served from the cache
(``compile_warm_hits``); a new entry means it compiled cold and is now
persisted for the next process.  The per-config summary is emitted as
a ``compile`` event (``prewarm=<config>`` field — ``roc_tpu.report``
renders the warm-vs-cold table from it) and returned.

Entry points:

- :func:`prewarm_config` — warm one registered rig config (the
  auditor's exact enumeration; ``python -m roc_tpu.prewarm`` drives
  this, one process per config with ``--jobs``).
- :func:`warm_candidates` — warm any candidate set (the serve export
  warms its bucket programs through it).
- :func:`write_warm_state` / :func:`load_warm_state` — the cached
  warm-state artifact (program-key sets per config), diffable against
  ``python -m roc_tpu.analysis --json`` to see whether a config's
  program set grew since the cache was warmed.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from ..obs.events import emit
from .compile_cache import enable_compile_cache

WARM_STATE_NAME = "programspace_warm.json"


def warm_state_path(path: Optional[str] = None) -> str:
    """The warm-state artifact location: explicit > the artifacts
    dir (ROC_TPU_BENCH_ARTIFACTS) > the repo's ``benchmarks/``."""
    if path:
        return path
    art = os.environ.get("ROC_TPU_BENCH_ARTIFACTS") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "benchmarks")
    return os.path.join(art, WARM_STATE_NAME)


def load_warm_state(path: Optional[str] = None) -> Dict[str, Any]:
    """{config: {"programs": n, "keys": [...], "t": iso}} recorded at
    the last prewarm; missing/corrupt file = no cached warm state."""
    try:
        with open(warm_state_path(path)) as f:
            db = json.load(f)
        return db if isinstance(db, dict) else {}
    except (OSError, ValueError):
        return {}


def write_warm_state(reports: List[Dict[str, Any]],
                     path: Optional[str] = None) -> str:
    """Merge per-config prewarm reports (carrying ``config`` and
    ``keys``) into the warm-state artifact; returns the path."""
    p = warm_state_path(path)
    state = load_warm_state(p)
    now = time.strftime("%Y-%m-%dT%H:%M:%S%z")
    for rep in reports:
        state[rep["config"]] = {
            "programs": len(rep.get("keys", [])),
            "keys": sorted(rep.get("keys", [])),
            "t": now,
        }
    os.makedirs(os.path.dirname(os.path.abspath(p)), exist_ok=True)
    tmp = p + ".tmp"
    with open(tmp, "w") as f:
        json.dump(state, f, indent=1, sort_keys=True)
    os.replace(tmp, p)
    return p


def warm_candidates(cands, cache_dir: str,
                    config: str = "trainer",
                    verbose: bool = False) -> Dict[str, Any]:
    """AOT-compile every candidate against the persistent cache at
    ``cache_dir`` (the directory ``enable_compile_cache`` returned).
    A candidate whose compile raises is recorded and skipped — a
    corrupt/stale cache entry must degrade to a live compile later,
    never crash the warmer (the cache is an optimization).  Failed
    candidates are excluded from ``keys`` so the warm-state artifact
    never marks a never-warmed program as warmed.  Warm-vs-cold
    attribution is listdir-diff based and exact for a single warmer
    per cache dir; concurrent warmers (``--jobs`` > 1) make it
    best-effort — a sibling's write inside this candidate's window
    counts as cold here (the key sets stay exact)."""
    from ..obs.compile_watch import program_key_of
    warm = cold = failed = 0
    t_start = time.perf_counter()
    slots: List[Dict[str, Any]] = []
    keys: List[str] = []
    for c in cands:
        before = set(os.listdir(cache_dir))
        t0 = time.perf_counter()
        try:
            c.aot()
        except Exception as e:  # noqa: BLE001 - degrade, not die
            failed += 1
            emit("compile", f"prewarm {config}:{c.slot} FAILED: "
                 f"{type(e).__name__}: {e}", console=verbose,
                 prewarm=config, slot=c.slot, error=str(e)[:200])
            continue
        # key recorded only AFTER a successful compile: a failed
        # candidate must show up as GROWTH against the warm state,
        # not be masked as already-warm
        keys.append(program_key_of(c.slot, c.args, c.donate))
        dt = time.perf_counter() - t0
        is_cold = bool(set(os.listdir(cache_dir)) - before)
        cold += is_cold
        warm += not is_cold
        slots.append({"slot": c.slot, "compile_s": round(dt, 3),
                      "cold": is_cold})
        emit("compile", f"prewarm {config}:{c.slot}: {dt:.2f}s "
             f"({'cold' if is_cold else 'warm hit'})",
             console=verbose, prewarm=config, slot=c.slot,
             compile_s=round(dt, 3), cold=is_cold)
    out = {"config": config, "programs": len(list(cands)),
           "compile_warm_hits": warm, "compile_cold": cold,
           "failed": failed,
           "prewarm_s": round(time.perf_counter() - t_start, 2),
           "cache_dir": cache_dir, "slots": slots, "keys": keys}
    emit("compile", f"prewarm {config}: {out['programs']} programs, "
         f"{warm} warm / {cold} cold"
         + (f" / {failed} failed" if failed else "")
         + f" in {out['prewarm_s']}s",
         prewarm=config, summary=True,
         programs=out["programs"], compile_warm_hits=warm,
         compile_cold=cold, failed=failed,
         prewarm_s=out["prewarm_s"])
    return out


def prewarm_config(name: str, dataset=None,
                   cache_dir: Optional[str] = None,
                   verbose: bool = False) -> Dict[str, Any]:
    """Warm one registered rig config against the persistent cache:
    builds the rig trainer (tables only — nothing compiles eagerly)
    and AOT-compiles the auditor's exact candidate set.  Returns the
    warm report (with the enumerated ``keys`` for the warm-state
    artifact); raises ``ValueError`` when the backend has fewer
    devices than the rig's mesh needs — a config that was asked for
    and not warmed is a failure, not a skip."""
    import jax

    from ..analysis.programspace import (build_rig_dataset,
                                         build_rig_trainer,
                                         candidate_programs,
                                         rig_configs,
                                         rig_required_devices)
    spec = rig_configs()[name]
    needed = rig_required_devices(spec)
    if needed > len(jax.devices()):
        raise ValueError(
            f"prewarm {name}: needs {needed} devices, the "
            f"{jax.default_backend()} backend has {len(jax.devices())} "
            f"(--cpu gives the 8-virtual-device rig)")
    d = enable_compile_cache(cache_dir, min_compile_secs=0.0)
    ds = dataset if dataset is not None else build_rig_dataset()
    tr = build_rig_trainer(spec, ds)
    return warm_candidates(candidate_programs(tr), d, config=name,
                           verbose=verbose)

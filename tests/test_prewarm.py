"""Compile-cache prewarm correctness (ISSUE 7): `python -m
roc_tpu.prewarm` completes on CPU inside the CI budget, a warm second
process records ZERO new-program compile events (program_key set
equality against the auditor's enumeration AND no new step-program
cache entries) on both rig configs, and a deliberately-stale cache
degrades gracefully (compile live, no crash).
"""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "prewarm_worker.py")

# cache entries of STEP programs (the ones prewarm must cover); the
# epoch loop's eager scalar ops (decayed_lr's power/divide, metric
# summaries) legitimately compile tiny fresh entries in any process
_STEP_ENTRY = re.compile(
    r"jit__?(step|train_step|eval_step|tail_|head_|apply_update)")


def _env(cache_dir, events=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["ROC_TPU_CACHE_MIN_SECS"] = "0"
    if events:
        env["ROC_TPU_EVENTS"] = events
    else:
        env.pop("ROC_TPU_EVENTS", None)
    return env


@pytest.fixture(scope="module")
def warmed(tmp_path_factory):
    """One CLI prewarm of every rig config into a fresh cache — the
    acceptance-criterion invocation (must finish on CPU < 90 s),
    shared by the warm-process and stale-cache tests."""
    root = tmp_path_factory.mktemp("prewarm")
    cache = str(root / "cache")
    state = str(root / "warm_state.json")
    os.makedirs(cache)
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.prewarm", "--config", "all",
         "--state", state],
        capture_output=True, text=True, timeout=90,
        env=_env(cache), cwd=_REPO)
    elapsed = time.time() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    reports = [json.loads(line) for line in r.stdout.splitlines()
               if line.strip().startswith("{")]
    return {"cache": cache, "state": state, "root": str(root),
            "reports": {rep["config"]: rep for rep in reports},
            "elapsed": elapsed}


def test_prewarm_cli_reports_and_state(warmed):
    """The CLI's JSON report lines + warm-state artifact: every rig
    warmed, every program cold on a fresh cache, key sets recorded."""
    assert warmed["elapsed"] < 90.0
    reps = warmed["reports"]
    assert set(reps) == {"gin_flat8", "sgc_stream", "sgc_serve",
                         "sgc_serve_q8", "gin_mesh2d"}
    for name, rep in reps.items():
        assert rep["programs"] > 0
        assert rep["compile_cold"] == rep["programs"], name
        assert rep["compile_warm_hits"] == 0
        assert rep["failed"] == 0
    state = json.load(open(warmed["state"]))
    assert set(state) == {"gin_flat8", "sgc_stream", "sgc_serve",
                          "sgc_serve_q8", "gin_mesh2d"}
    for name in state:
        assert state[name]["programs"] == reps[name]["programs"]
        assert len(state[name]["keys"]) == reps[name]["programs"]
    assert os.listdir(warmed["cache"]), "cache stayed empty"


def test_second_prewarm_all_warm(warmed):
    """Idempotence: re-warming against the populated cache reports
    every program as a warm hit (file-based cold detection)."""
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.prewarm", "--config",
         "sgc_stream", "--no-state"],
        capture_output=True, text=True, timeout=90,
        env=_env(warmed["cache"]), cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    rep = next(json.loads(line) for line in r.stdout.splitlines()
               if line.strip().startswith("{"))
    assert rep["compile_cold"] == 0
    assert rep["compile_warm_hits"] == rep["programs"]


@pytest.mark.parametrize("name", ["gin_flat8", "sgc_stream"])
def test_warm_second_process_zero_new_programs(warmed, name):
    """THE acceptance criterion: a warm second process running the
    full live lifecycle (train+eval+predict) compiles ZERO new
    programs — its compile events' program_key set equals the
    auditor's enumeration exactly, and not one new STEP-program entry
    appears in the persistent cache (the eager epoch-loop scalars are
    the only permitted new entries)."""
    events = os.path.join(warmed["root"], f"ev_{name}.jsonl")
    before = set(os.listdir(warmed["cache"]))
    r = subprocess.run(
        [sys.executable, _WORKER, name],
        capture_output=True, text=True, timeout=240,
        env=_env(warmed["cache"], events=events), cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WORKER_OK" in r.stdout
    new = set(os.listdir(warmed["cache"])) - before
    new_steps = sorted(f for f in new if _STEP_ENTRY.search(f))
    assert not new_steps, (
        f"{name}: warm process compiled NEW step programs: "
        f"{new_steps}")
    live = {json.loads(line).get("program_key")
            for line in open(events)
            if '"cat": "compile"' in line}
    live.discard(None)
    from roc_tpu.analysis.programspace import (enumerate_programs,
                                               rig_configs)
    space = enumerate_programs(rig_configs()[name])
    assert live == space.observed_keys(), (
        f"{name}: live-only={sorted(live - space.observed_keys())} "
        f"static-only={sorted(space.observed_keys() - live)}")


def test_stale_cache_degrades_gracefully(warmed):
    """Corrupt every persisted executable: the live process must fall
    back to compiling fresh — no crash, training completes.  (The
    cache is an optimization; a stale/torn dir must never be fatal.)"""
    stale = os.path.join(warmed["root"], "stale_cache")
    shutil.copytree(warmed["cache"], stale)
    for f in os.listdir(stale):
        with open(os.path.join(stale, f), "wb") as fh:
            fh.write(b"\x00corrupt\xff" * 8)
    r = subprocess.run(
        [sys.executable, _WORKER, "sgc_stream"],
        capture_output=True, text=True, timeout=240,
        env=_env(stale), cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WORKER_OK" in r.stdout


def test_jobs_without_cpu_is_refused():
    """An accelerator belongs to one process: parallel children are a
    CPU-only mode, refused up front instead of failing backend init
    one child at a time."""
    from roc_tpu import prewarm
    assert prewarm.main(["--jobs", "2"]) == 2


def test_rig_the_backend_cannot_host_fails(tmp_path):
    """A rig that needs more devices than the backend has is an
    error in the exit code, not a skip that exits 0."""
    env = _env(str(tmp_path / "cache"))
    env["XLA_FLAGS"] = ""           # one CPU device: parts=2 cannot fit
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.prewarm", "--config",
         "gin_flat8", "--no-state"],
        capture_output=True, text=True, timeout=120, env=env, cwd=_REPO)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "needs 2 devices" in r.stderr

"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
bench/tests -q``.  Not part of ``tests/`` (tier-1 is untouched).  They
run the harness end to end on a tiny fixture table of cells under
``--rehearsal``, so no CPU number sits under a device metric's name."""

import json
import os
import subprocess
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(TESTS, "fixtures")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="session")
def work(tmp_path_factory):
    """Datasets, traces and the compile cache of every run in this
    session: outside the checkout."""
    return str(tmp_path_factory.mktemp("bench"))


def run_cell(work, workload, *extra, benchmark=None, rehearsal=True,
             env=None):
    """``bench/run.py`` in a process of its own, as the driver runs it.
    Returns (exit code, stdout lines that are JSON objects, stderr)."""
    cmd = [sys.executable, os.path.join(BENCH, "run.py"),
           "--workload", workload, "--seed", "1", "--seconds", "1",
           "--benchmark",
           benchmark or os.path.join(FIXTURES, "BENCHMARK.json"),
           "--data-dir", os.path.join(work, "data"), *extra]
    if rehearsal:
        cmd.append("--rehearsal")
    full_env = {**os.environ,
                "JAX_COMPILATION_CACHE_DIR": os.path.join(work, "cache"),
                **(env or {})}
    full_env.pop("XLA_FLAGS", None)
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=full_env, cwd=ROOT, check=False)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return p.returncode, lines, p.stderr

"""The command end to end on the fixture cells, in a process of its
own, under ``--rehearsal``: one device and four virtual ones."""

import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, CONTRACT_KEYS, ROOT, run_cell


def last(lines):
    assert lines, "no JSON line on stdout"
    return lines[-1]


def test_one_chip_result_line_has_exactly_the_contract_keys(work):
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "0")
    assert rc == 0, err[-2000:]
    res = last(lines)
    assert set(res) == CONTRACT_KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 6
    assert set(res["metrics"]) == {"epoch_ms", "eval_ms", "setup_s"}
    # rehearsal: the platform reads cpu and no timing or memory value
    # sits under a device metric's name
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": None}
    assert all(m["value"] is None for m in res["metrics"].values())
    earlier = {k for ln in lines[:-1] for k in ln}
    assert {"versions", "plan", "setup_split_s", "samples",
            "check"} <= earlier
    samples = next(ln for ln in lines if "samples" in ln)
    assert samples["compiles_in_window"] == 0
    assert samples["last_train_loss"] < samples["untrained_loss"]
    assert samples["samples"]["epochs"] == 5 * samples["samples"]["bursts"]


def test_second_run_finds_every_program_in_the_cache(work):
    run_cell(work, "tiny-gcn.fullgraph", "--trace", "0")
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "0")
    assert rc == 0, err[-2000:]
    setup = next(ln for ln in lines if "setup_split_s" in ln)
    assert setup["topology_cached"] is True
    assert setup["compile_cache"]["misses"] == 0
    assert setup["compile_cache"]["new_entries"] == 0


def test_four_devices_traced_run(work):
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph-p4", "--trace", "1")
    assert rc == 0, err[-2000:]
    res = last(lines)
    assert set(res) == CONTRACT_KEYS | {"breakdown"}
    assert res["correct"] is True
    assert res["device"]["count"] == 4
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert {"device_idle_share", "compiles_in_window", "host_build_s",
            "compile_s", "collective_ms",
            "collective_exposed_share"} <= set(res["metrics"])
    assert "agg_ms" not in res["metrics"]        # a one-chip metric
    assert res["metrics"]["compiles_in_window"] == {"value": 0,
                                                    "unit": "count"}
    assert all(m["value"] is None for k, m in res["metrics"].items()
               if k != "compiles_in_window")
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    plan = next(ln for ln in lines if "plan" in ln)
    assert plan["plan"]["num_parts"] == 4
    assert plan["placement"]["ok"] is True


def test_one_chip_traced_run_reads_the_aggregation(work):
    rc, lines, err = run_cell(work, "tiny-sage.fullgraph", "--trace", "1")
    assert rc == 0, err[-2000:]
    res = last(lines)
    assert res["correct"] is True
    assert "agg_ms" in res["metrics"]
    assert "collective_ms" not in res["metrics"]


def test_bfloat16_system_fails_the_float32_tolerance(work):
    rc, lines, err = run_cell(work, "tiny-gcn-bf16.fullgraph",
                              "--trace", "0")
    assert rc == 0, err[-2000:]
    assert last(lines)["correct"] is False
    check = next(ln for ln in lines if "check" in ln)["check"]
    assert check["ok"] is False and check["finite"] is True


def test_without_a_chip_nothing_runs_and_nothing_is_printed(work):
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "0",
                              rehearsal=False)
    assert rc == 2 and lines == []
    assert "no accelerator" in err and "'cpu'" in err


def test_unknown_cell_is_refused(work):
    rc, lines, err = run_cell(work, "no-such.cell")
    assert rc == 2 and lines == []
    assert "no-such.cell" in err


def test_alone_without_the_program_it_exits_non_zero(work, tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: no result, exit code other than 0."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gcn-reddit.fullgraph", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearsal"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        check=False)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "roc_tpu" in p.stderr

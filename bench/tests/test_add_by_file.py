"""A later PR adds a cell, a traffic mix, a configuration and a
per-layer metric as new files and new ``BENCHMARK.json`` entries, and
edits no file that is there."""

import hashlib
import json
import os
import shutil

from conftest import FIXTURES, run_cell


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_picked_up_with_no_edit(work, tmp_path):
    table = tmp_path / "table"
    shutil.copytree(FIXTURES, table,
                    ignore=shutil.ignore_patterns("traces"))
    before = digest(table)

    def write(rel, obj):
        path = table / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(obj if isinstance(obj, str) else json.dumps(obj))

    def read(rel):
        return json.loads((table / rel).read_text())

    cfg = read("configs/tiny-gcn.json")
    cfg.update(name="wide-gcn", cli=[
        a if a != "32-16-7" else "32-24-7" for a in cfg["cli"]])
    cfg["model"]["layers"] = [32, 24, 7]
    write("configs/wide-gcn.json", cfg)
    mix = read("traffic/fullgraph.json")
    mix.update(name="every-third", eval_every=3)
    write("traffic/every-third.json", mix)
    cell = read("workloads/tiny-gcn.fullgraph.json")
    write("workloads/wide-gcn.every-third.json", cell)
    write("layer_metrics/window_bursts.py",
          '"""A metric a later PR brings: bursts in the window."""\n\n\n'
          "def read(run):\n    return len(run.bursts)\n")
    bench = read("BENCHMARK.json")
    bench["configs"].append({"name": "wide-gcn", "source": "fixture",
                             "file": "configs/wide-gcn.json",
                             "reduced": [], "why": "added by file"})
    bench["workloads"].append({"name": "wide-gcn.every-third",
                               "config": "wide-gcn",
                               "traffic": "every-third", "chips": 1,
                               "why": "added by file"})
    bench["per_layer"].append({
        "name": "window_bursts", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "step_loop",
        "moves": "epoch_ms", "workloads": ["wide-gcn.every-third"]})
    new_bench = tmp_path / "BENCHMARK.new.json"
    # beside the table, with paths pointing at it: the copied
    # BENCHMARK.json stays as it was
    bench["paths"] = ["table"]
    for c in bench["configs"]:
        c["file"] = "table/" + c["file"]
    new_bench.write_text(json.dumps(bench))

    rc, lines, err = run_cell(work, "wide-gcn.every-third", "--trace", "1",
                              benchmark=str(new_bench))
    assert rc == 0, err[-2000:]
    res = lines[-1]
    assert res["correct"] is True
    samples = next(ln for ln in lines if "samples" in ln)["samples"]
    assert samples["epochs"] == 3 * samples["bursts"]
    assert res["metrics"]["window_bursts"] == {
        "value": samples["bursts"], "unit": "count"}
    # an old cell does not report the new metric, and still runs
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "1",
                              benchmark=str(new_bench))
    assert rc == 0, err[-2000:]
    assert "window_bursts" not in lines[-1]["metrics"]
    after = digest(table)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/wide-gcn.json", "traffic/every-third.json",
        "workloads/wide-gcn.every-third.json",
        "layer_metrics/window_bursts.py"}

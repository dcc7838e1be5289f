"""Set-up by phase (``layer_metrics/_setup_spans.py`` and its five
readers): the reduction on a hand-written events file whose sums are
known, a program that flushes no batch (a parent commit), the five
entries found by name in the accepted table, and the rehearsal of the
fixture cells through a fixture table that has them."""

import json
import os

import pytest

from conftest import BENCH, FIXTURES, ROOT, run_cell

from harness import cells

TABLE = os.path.join(FIXTURES, "BENCHMARK.setup_spans.json")
EVENTS = os.path.join(FIXTURES, "setup_spans.events.jsonl")
METRICS = {"setup_load_s": "host_table_build", "setup_resolve_s": "entry",
           "setup_tables_s": "host_table_build",
           "setup_upload_s": "host_table_build",
           "setup_unspanned_share": "host_table_build"}
BUILD_S = 13.0


def _load(path):
    with open(path) as f:
        return json.load(f)


def make_run(events_path, rehearsal=False):
    cell = cells.load_cell(TABLE, "tiny-gcn.fullgraph")
    run = cell.module("drivers", "train_job").Run(
        cell=cell, args=None, devs=[], rehearsal=rehearsal, peaks=None,
        events_path=events_path)
    run.seconds["build_s"] = BUILD_S
    return run


def read_all(run):
    return {name: run.cell.module("layer_metrics", name).read(run)
            for name in METRICS}


def test_tree_self_time_and_counters_of_a_known_file(capsys):
    run = make_run(EVENTS)
    got = run.cell.module("layer_metrics", "_setup_spans").measure(run)
    rows = got["rows"]
    # a lap's self time: its duration less what its children cover
    assert rows[("setup.load", None)][:3] == [
        1, pytest.approx(2.0), pytest.approx(0.1)]
    assert rows[("setup.load.graph", "setup.load")][3] == {
        "file_bytes": 4000}
    # entered twice, the second time with one child only
    assert rows[("setup.resolve", None)][:3] == [
        2, pytest.approx(0.6), pytest.approx(0.12)]
    assert rows[("setup.resolve.plan", "setup.resolve")][:2] == [
        2, pytest.approx(0.38)]
    assert rows[("setup.symmetry", None)][3] == {"edges": 40,
                                                 "symmetric": 1}
    assert rows[("setup.tables", None)][:3] == [
        2, pytest.approx(3.5), pytest.approx(3.5)]
    assert rows[("setup.upload", None)][3] == {"h2d_bytes": 1_500_000_000}
    assert got["labels"][("setup.tables", "sectioned")] == [
        1, pytest.approx(3.0), {"edges": 40, "sub_rows": 10}]
    # the epoch loop's laps and the repartition batch are not set-up
    assert all(name.startswith("setup.") for name, _ in rows)
    assert got["top_s"] == pytest.approx(11.501)

    values = read_all(run)
    assert values == {
        "setup_load_s": pytest.approx(2.0),
        "setup_resolve_s": pytest.approx(0.6 + 4.0 + 0.4),
        "setup_tables_s": pytest.approx(3.5),
        "setup_upload_s": pytest.approx(0.75 + 0.25 + 0.001),
        "setup_unspanned_share": pytest.approx(
            100 * (BUILD_S - 11.501) / BUILD_S)}
    # the four phases and what no span covers add up to build_s
    unspanned_s = values["setup_unspanned_share"] / 100 * BUILD_S
    assert sum(v for k, v in values.items() if k.endswith("_s")) \
        + unspanned_s == pytest.approx(BUILD_S)

    # one line, however many readers asked
    (line,) = [json.loads(ln)["setup_spans"]
               for ln in capsys.readouterr().out.splitlines()]
    assert line["build_s"] == BUILD_S
    assert line["top_s"] == pytest.approx(11.501)
    assert line["h2d_gb_per_s"] == pytest.approx(1.5 / 0.75)
    assert ["setup.symmetry", None, 1, pytest.approx(4.0),
            pytest.approx(4.0), {"edges": 40, "symmetric": 1}] in line["rows"]
    assert ["setup.upload", "features", 1, pytest.approx(0.25),
            {"h2d_bytes": 500_000_000}] in line["by_label"]


def test_rehearsal_nulls_the_timings_and_keeps_the_counts(capsys):
    run = make_run(EVENTS, rehearsal=True)
    run.cell.module("layer_metrics", "_setup_spans").measure(run)
    (line,) = [json.loads(ln)["setup_spans"]
               for ln in capsys.readouterr().out.splitlines()]
    assert (line["top_s"], line["build_s"], line["h2d_gb_per_s"]) == (
        None, None, None)
    assert all(row[3] is None and row[4] is None and row[2] >= 1
               for row in line["rows"])
    assert ["setup.load", None, 1, None, None,
            {"nodes": 10, "edges": 40}] in line["rows"]


def test_a_program_without_the_batch_gives_nothing(tmp_path, capsys):
    """A parent commit flushes no set-up batch: no line, no metric, no
    error — with the events file there or not."""
    with open(EVENTS) as f:
        kept = [ln for ln in f if '"phase": "setup"' not in ln]
    assert len(kept) == 5
    old = tmp_path / "parent.events.jsonl"
    old.write_text("".join(kept))
    for path in (str(old), str(tmp_path / "none.jsonl")):
        assert read_all(make_run(path)) == dict.fromkeys(METRICS)
    assert capsys.readouterr().out == ""


def test_the_five_entries_are_in_the_tables_by_name():
    """By name: entries that later PRs append do not move them."""
    for path in (os.path.join(ROOT, "BENCHMARK.json"), TABLE):
        by_name = {m["name"]: m for m in _load(path)["per_layer"]}
        for name, layer in METRICS.items():
            assert by_name[name] == {
                "name": name, "unit": "%" if name.endswith("share") else "s",
                "better": "lower", "source": "program_span", "layer": layer,
                "moves": "setup_s"}
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", name + ".py"))
    # the fixture table is the step-scopes one and these five
    a = _load(TABLE)
    b = _load(os.path.join(FIXTURES, "BENCHMARK.step_scopes.json"))
    a["per_layer"] = [m for m in a["per_layer"] if m["name"] not in METRICS]
    assert a == b


@pytest.mark.parametrize("cell,top,tables", [
    ("tiny-gcn.fullgraph",
     {"setup.load", "setup.resolve", "setup.symmetry", "setup.tables",
      "setup.upload", "setup.params", "setup.steps", "setup.manifest"},
     {"ell"}),
    ("tiny-gcn.fullgraph-p4",
     {"setup.load", "setup.resolve", "setup.symmetry", "setup.partition",
      "setup.tables", "setup.upload", "setup.params", "setup.steps",
      "setup.manifest"},
     {"ell", "edge_list", "padded_rows"})])
def test_rehearsal_prints_the_line_and_the_five_metrics(work, cell, top,
                                                        tables):
    rc, lines, err = run_cell(work, cell, "--trace", "1", benchmark=TABLE)
    assert rc == 0, err[-2000:]
    result = lines[-1]
    assert result["correct"] is True
    for name in METRICS:                  # there, with the timing null
        assert result["metrics"][name]["value"] is None
    (line,) = [ln["setup_spans"] for ln in lines if "setup_spans" in ln]
    assert lines.index({"setup_spans": line}) < len(lines) - 1
    assert (line["top_s"], line["build_s"], line["h2d_gb_per_s"]) == (
        None, None, None)
    assert {row[0] for row in line["rows"] if row[1] is None} == top
    assert all(row[3] is None and row[4] is None and row[2] >= 1
               for row in line["rows"])
    by_name = {(row[0], row[1]): row[5] for row in line["rows"]}
    assert by_name[("setup.upload", None)]["h2d_bytes"] > 0
    assert by_name[("setup.params", None)]["param_bytes"] > 0
    assert by_name[("setup.load", None)]["edges"] > 0
    assert by_name[("setup.load.features", "setup.load")]["file_bytes"] > 0
    assert {lab for name, lab, *_ in line["by_label"]
            if name == "setup.tables"} == tables | {"ell_w"}

"""``rel_train_slot_share``, the per-layer metric that counts what the
loss program of a typed model still scans of its relation tables
(``layer_metrics/rel_train_slot_share.py``): the reader on hand-built
plans — one with the ``train_`` keys, a parent commit's without them, a
program with no relation, a layout that counts no backward slots — the
entry in the repo's table and in the fixture table, and the tiny typed
cell end to end under ``--rehearsal``, where the ``plan`` line carries
the counts the reader reads."""

import json
import os
from types import SimpleNamespace

import pytest

from conftest import FIXTURES, ROOT, run_cell

from harness import cells

TABLE = os.path.join(FIXTURES, "BENCHMARK.rel_train.json")
NAME = "rel_train_slot_share"
CELL = "rgcn-mag.fullgraph-typed"
CHUNK = 8 * 8192
# ogbn-mag's tables: 885 / 755 chunks a forward / backward pass, of
# which the relations into papers fill 517 / 500
WHOLE = {"slots_fwd": 885 * CHUNK, "slots_bwd": 755 * CHUNK}
LAYERS = [
    {"op": 2, "layer": 0, **WHOLE, "train_relations": 7,
     "train_slots_fwd": 885 * CHUNK, "train_slots_bwd": 755 * CHUNK},
    {"op": 8, "layer": 1, **WHOLE, "train_relations": 3,
     "train_slots_fwd": 517 * CHUNK, "train_slots_bwd": 500 * CHUNK}]


def load(path):
    with open(path) as f:
        return json.load(f)


def _read(resolved):
    cell = cells.load_cell(TABLE, "tiny-rgcn.fullgraph-typed")
    run = SimpleNamespace(cell=cell, scratch={"resolved": resolved})
    return cell.module("layer_metrics", NAME).read(run)


def test_share_of_the_slots_the_loss_program_gathers():
    got = _read({"rel_layers": LAYERS})
    assert got == pytest.approx(100 * (885 + 755 + 517 + 500)
                                / (2 * (885 + 755)))
    assert round(got, 1) == 81.0


def test_a_parents_plan_counts_its_own_slots():
    bare = [{k: v for k, v in l.items() if not k.startswith("train_")}
            for l in LAYERS]
    assert _read({"rel_layers": bare}) == 100.0
    # one layer with the keys, one without
    assert _read({"rel_layers": [bare[0], LAYERS[1]]}) == \
        _read({"rel_layers": LAYERS})


@pytest.mark.parametrize("resolved", [
    None, {}, {"aggr_impl": "sectioned"}, {"rel_layers": []},
    # the edge-list reference has no backward table to count
    {"rel_layers": [{**LAYERS[0], "slots_bwd": None,
                     "train_slots_bwd": None}]},
    {"rel_layers": [{**LAYERS[1], "train_slots_bwd": None}]}])
def test_nothing_to_read(resolved):
    assert _read(resolved) is None


def test_entry_is_appended_last_with_its_reader():
    mine = load(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"][-1]
    assert mine == {"name": NAME, "unit": "%", "better": "lower",
                    "source": "program_counter", "layer": "aggregation",
                    "moves": "epoch_ms", "workloads": [CELL]}
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert os.path.isfile(cell.find("layer_metrics", NAME, ".py"))
    assert NAME in {m["name"] for m in cell.metrics("per_layer")}


def test_fixture_table_is_the_rgcn_one_plus_this_metric():
    a, b = load(TABLE), load(os.path.join(FIXTURES, "BENCHMARK.rgcn.json"))
    assert [m["name"] for m in a["per_layer"][len(b["per_layer"]):]] == \
        [NAME]
    a["per_layer"] = a["per_layer"][:len(b["per_layer"])]
    assert a == b


def test_tiny_cell_end_to_end(work):
    rc, lines, err = run_cell(work, "tiny-rgcn.fullgraph-typed",
                              "--trace", "1", benchmark=TABLE)
    assert rc == 0, err[-2000:]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    # a rehearsal prints no value that is not a count
    assert result["metrics"][NAME] == {"value": None, "unit": "%"}
    plan = next(ln for ln in lines if "plan" in ln)["plan"]
    first, last = plan["rel_layers"]
    assert (first["train_relations"], last["train_relations"]) == (7, 3)
    assert first["train_edges"] == plan["relation_edges"] == 16800
    assert last["train_edges"] == sum(
        r["edges"] for r in plan["relations"] if r["dst"] == 0)
    assert (first["train_out_rows"], last["train_out_rows"]) == (830, 300)
    assert (first["train_slots_fwd"], first["train_slots_bwd"]) == \
        (first["slots_fwd"], first["slots_bwd"])
    assert last["train_slots_fwd"] < last["slots_fwd"]
    assert last["train_slots_bwd"] < last["slots_bwd"]
    cell = cells.load_cell(TABLE, "tiny-rgcn.fullgraph-typed")
    share = cell.module("layer_metrics", NAME).read(
        SimpleNamespace(cell=cell, scratch={"resolved": plan}))
    assert 50 < share < 100
    # an old cell of the same table does not read it
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "1",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    assert NAME not in lines[-1]["metrics"]

"""``agg_slot_roundtrip_share``, the per-layer metric that reads how many
of the slots the sum chunk scans gather still go through HBM
(``layer_metrics/agg_slot_roundtrip_share.py``): the reader on
hand-built ``manifest`` events — every table fused, some, none; a
parent commit's plan without the key; programs that scan no chunk —
and the entry in the repo's table, found by name."""

import json
import os
from types import SimpleNamespace

import pytest

from conftest import FIXTURES, ROOT

from harness import cells

NAME = "agg_slot_roundtrip_share"
CELLS = ["gcn-reddit.fullgraph", "gcn-products.fullgraph-p4",
         "gcn2-arxiv.fullgraph", "rgcn-mag.fullgraph-typed",
         "deepergcn-arxiv.fullgraph"]
SEG = 131_072
# Reddit under `sectioned`: three sections of 32 chunks, one of 18,
# each held in VMEM by the kernel
REDDIT = {"aggr_impl": "sectioned",
          "agg_chunk_rows": [[32, SEG]] * 3 + [[18, SEG]],
          "agg_gather_sum": [["fused", 32 * SEG * 8]] * 3
          + [["fused", 18 * SEG * 8]]}


def _read(resolved):
    cell = cells.load_cell(
        os.path.join(FIXTURES, "BENCHMARK.step_scopes.json"),
        "tiny-gcn.fullgraph")
    run = SimpleNamespace(cell=cell, scratch={"resolved": resolved})
    return cell.module("layer_metrics", NAME).read(run)


def test_reads_0_when_every_table_is_fused():
    assert _read(REDDIT) == 0.0


def test_weighs_the_tables_by_their_slots():
    mixed = dict(REDDIT, agg_gather_sum=[
        ["fused", 3 * SEG * 8], ["two_pass", SEG * 8]])
    assert _read(mixed) == pytest.approx(25.0)


@pytest.mark.parametrize("resolved", [
    # tables the kernel cannot hold: products' flat table in HBM
    {"aggr_impl": "flat_sum", "agg_chunk_rows": [[515, 8192]],
     "agg_gather_sum": [["two_pass", 515 * 8192 * 8]]},
    # a parent commit: chunks scanned, no such key
    {"aggr_impl": "sectioned", "agg_chunk_rows": [[32, SEG]]},
    {"aggr_impl": "flat_sum", "agg_chunk_rows": [[515, 8192]]},
    # a parent's typed program: its scans are the relation passes
    {"aggr_impl": "flat_sum", "agg_chunk_rows": [],
     "rel_layers": [{"slots_fwd": 885 * 65536, "slots_bwd": 755 * 65536}]},
])
def test_reads_100_where_nothing_is_fused_or_the_key_is_absent(resolved):
    assert _read(resolved) == 100.0


@pytest.mark.parametrize("resolved", [
    None, {}, {"aggr_impl": "ell", "agg_chunk_rows": []},
    {"aggr_impl": "ell", "agg_chunk_rows": [], "agg_gather_sum": []},
    # attention alone
    {"aggr_impl": "ell", "attention": [{"op": 3, "layout": "ell"}]},
    # the edge-list reference counts no slots
    {"aggr_impl": "segment", "rel_layers": [{"slots_fwd": None}]}])
def test_no_chunk_scan_gives_nothing_to_read(resolved):
    assert _read(resolved) is None


def test_entry_is_in_the_table_by_name_with_its_reader():
    """By name: entries that later PRs append do not move it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        table = json.load(f)
    by_name = {m["name"]: m for m in table["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "aggregation",
        "moves": "epoch_ms", "workloads": CELLS}
    for name in CELLS:
        cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), name)
        assert os.path.isfile(cell.find("layer_metrics", NAME, ".py"))
        assert NAME in {m["name"] for m in cell.metrics("per_layer")}
        assert "epoch_ms" in {m["name"] for m in cell.metrics("end_to_end")}
    other = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                            "gat-arxiv.fullgraph")
    assert NAME not in {m["name"] for m in other.metrics("per_layer")}

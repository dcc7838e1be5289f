"""``agg_carry_update_share``, the per-layer metric that reads how far
the chunk scan's segmented sum cut the rows a pass adds into its carry
one by one (``layer_metrics/agg_carry_update_share.py``): the reader on
hand-built ``manifest`` events — the rule engaged on every section, on
some, on none; a parent commit's plan without the keys; programs that
scan no chunk — and the entry in the repo's table, found by name."""

import json
import os
from types import SimpleNamespace

import pytest

from conftest import FIXTURES, ROOT

from harness import cells

NAME = "agg_carry_update_share"
CELLS = ["gcn-reddit.fullgraph", "gcn-products.fullgraph-p4",
         "gcn2-arxiv.fullgraph", "rgcn-mag.fullgraph-typed"]
SEG = 131_072
# Reddit under `sectioned`: three sections of 32 chunks at tiles of
# 2,048 sub-rows spanning 192 rows, one of 18 at 1,024 spanning 160
REDDIT = {"aggr_impl": "sectioned",
          "agg_chunk_rows": [[32, SEG]] * 3 + [[18, SEG]],
          "agg_seg_sum": [[2048, 192]] * 3 + [[1024, 160]],
          "agg_carry_updates": [[32 * SEG, 32 * 64 * 192]] * 3
          + [[18 * SEG, 18 * 128 * 160]]}


def _manifest(resolved):
    return {"cat": "manifest", "resolved": resolved}


def _read(event):
    cell = cells.load_cell(
        os.path.join(FIXTURES, "BENCHMARK.step_scopes.json"),
        "tiny-gcn.fullgraph")
    run = SimpleNamespace(cell=cell,
                          scratch={"resolved": event.get("resolved")})
    return cell.module("layer_metrics", NAME).read(run)


def test_engaged_on_every_section():
    got = _read(_manifest(REDDIT))
    assert got == pytest.approx(
        100 * (3 * 32 * 64 * 192 + 18 * 128 * 160) / (114 * SEG))
    assert 9 < got < 13


def test_engaged_on_some_sections():
    mixed = dict(REDDIT, agg_carry_updates=[
        [32 * SEG, 32 * 64 * 192], [32 * SEG, 32 * SEG]])
    assert _read(_manifest(mixed)) == pytest.approx(
        100 * (64 * 192 + SEG) / (2 * SEG))


@pytest.mark.parametrize("resolved", [
    # the rule read the tables and kept the scatter everywhere
    {"aggr_impl": "sectioned", "agg_chunk_rows": [[2, 106496]] * 2,
     "agg_seg_sum": [None, None],
     "agg_carry_updates": [[212992, 212992]] * 2},
    # a parent commit: chunks scanned, no such key
    {"aggr_impl": "sectioned", "agg_chunk_rows": [[32, SEG]]},
    {"aggr_impl": "flat_sum", "agg_chunk_rows": [[515, 8192]]},
    # a parent's typed program: its scans are the relation passes
    {"aggr_impl": "flat_sum", "agg_chunk_rows": [],
     "rel_layers": [{"slots_fwd": 885 * 65536, "slots_bwd": 755 * 65536}]},
])
def test_reads_100_where_nothing_engaged_or_the_keys_are_absent(resolved):
    assert _read(_manifest(resolved)) == 100.0


@pytest.mark.parametrize("resolved", [
    None, {}, {"aggr_impl": "ell", "agg_chunk_rows": []},
    {"aggr_impl": "ell", "agg_chunk_rows": [], "agg_carry_updates": [],
     "agg_seg_sum": []},
    # the edge-list reference counts no slots
    {"aggr_impl": "segment", "rel_layers": [{"slots_fwd": None}]}])
def test_no_chunk_scan_gives_nothing_to_read(resolved):
    assert _read(_manifest(resolved)) is None


def test_entry_is_in_the_table_by_name_with_its_reader():
    """By name: entries that later PRs append do not move it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        table = json.load(f)
    by_name = {m["name"]: m for m in table["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "aggregation",
        "moves": "epoch_ms", "workloads": CELLS}
    # every listed cell reports the end-to-end metric it moves, and
    # finds the reader beside the others
    for name in CELLS:
        cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), name)
        assert os.path.isfile(cell.find("layer_metrics", NAME, ".py"))
        assert NAME in {m["name"] for m in cell.metrics("per_layer")}
        assert "epoch_ms" in {m["name"] for m in cell.metrics("end_to_end")}
    other = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                            "gat-arxiv.fullgraph")
    assert NAME not in {m["name"] for m in other.metrics("per_layer")}

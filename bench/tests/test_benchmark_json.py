"""``BENCHMARK.json`` against the contract's own limits, so a later PR
that adds an entry sees a refusal here and not on the chip."""

import json
import os
import re

import pytest

from conftest import FIXTURES, ROOT

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj",
               "head", "expansion", "experts_per")


@pytest.fixture(scope="module", params=[
    os.path.join(ROOT, "BENCHMARK.json"),
    os.path.join(ROOT, "bench", "unadmitted.json"),
    os.path.join(FIXTURES, "BENCHMARK.json")],
    ids=["repo", "unadmitted", "fixture"])
def table(request):
    with open(request.param) as f:
        return request.param, json.load(f)


def test_keys_sizes_and_names(table):
    path, b = table
    assert set(b) == KEYS
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= len(b["command"]) <= 32
    assert 1 <= len(b["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/")
               and ".." not in p.split("/") for p in b["paths"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["configs"]) <= 24
    assert len(b["workloads"]) <= 24
    assert len(b["workloads"]) >= 2 or "unadmitted" in path
    assert 1 <= len(b["end_to_end"]) <= 16
    assert 1 <= len(b["per_layer"]) <= 128
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert all(len(x["why"]) <= 200
               for k in ("configs", "workloads") for x in b[k])


def test_full_check_fits_the_time_limit(table):
    _, b = table
    runs = 2 + 14 * 24                      # a later PR may fill 24 cells
    assert (runs * (b["run_seconds"] + 60) + 24 * 180 + 1200) <= 43200


def test_configs_and_cells_fit_together(table):
    path, b = table
    base = os.path.dirname(path)
    roots = [os.path.join(base, p) for p in b["paths"]]
    configs = {c["name"]: c for c in b["configs"]}
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    for c in b["configs"]:
        full = os.path.normpath(os.path.join(base, c["file"]))
        assert os.path.isfile(full), full
        assert any(full.startswith(os.path.normpath(r) + os.sep)
                   for r in roots)
        assert not any(w in key.lower() or key.endswith(("_dim", "_rank"))
                       for key in c["reduced"] for w in WIDTH_WORDS)
        with open(full) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert {"cli", "model", "reference", "graph", "assumed"} <= set(body)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert all(w["chips"] in (1, 4) for w in b["workloads"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        for kind, name in (("workloads", w["name"]),
                           ("traffic", w["traffic"])):
            assert any(os.path.isfile(os.path.join(r, kind, name + ".json"))
                       for r in roots), (kind, name)


def test_metrics(table):
    path, b = table
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("lower", "higher")
    bench_dir = os.path.join(ROOT, "bench")
    roots = [os.path.join(os.path.dirname(path), p) for p in b["paths"]]
    for m in b["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "bound" not in m and LAYER.match(m["layer"]), m["layer"]
        assert any(os.path.isfile(os.path.join(
            r, "layer_metrics", m["name"] + ".py"))
            for r in roots + [bench_dir]), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in b["end_to_end"] + b["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m["name"] for m in b["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in b["per_layer"]
                 if cell in m.get("workloads", cells)]
        assert layer and all(m["moves"] in mine for m in layer)

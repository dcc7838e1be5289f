"""Set-up under spans (``roc_tpu/obs/events.py span`` / ``flush_spans``):
the helper's own arithmetic, the batch a trainer's constructor flushes,
what ``python -m roc_tpu.timeline`` makes of it, and — through
``cli.main`` on the synthetic dataset — which spans each build path
enters, that they fit inside the wall around the call, and that the
programs a run lowers do not depend on a sink being there."""

import hashlib
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from roc_tpu.obs import events
from roc_tpu.obs.events import flush_spans, span

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bus():
    """A bus of this test's own, with no sink; the global one (and the
    environment ``cli.main --events`` sets) is put back after."""
    old_env = os.environ.get("ROC_TPU_EVENTS")
    os.environ.pop("ROC_TPU_EVENTS", None)
    yield events.configure(console=False)
    events.configure(jsonl_path=None)
    if old_env is not None:
        os.environ["ROC_TPU_EVENTS"] = old_env
    else:
        os.environ.pop("ROC_TPU_EVENTS", None)


@pytest.fixture(scope="module")
def reduce_laps():
    """The benchmark's reduction of a batch (stdlib only), so the self
    time the readers report is held to the helper's records here."""
    spec = importlib.util.spec_from_file_location(
        "setup_spans_under_test", os.path.join(
            _REPO, "bench", "layer_metrics", "_setup_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce_laps


def _batches(records, phase="setup"):
    return [r for r in records if r.get("cat") == "timeline"
            and r.get("kind") == "spans" and r.get("phase") == phase]


# ------------------------------------------------------------ the helper

def test_nesting_gives_parent_and_self_time(bus, reduce_laps):
    with span("outer"):
        time.sleep(0.02)
        with span("inner"):
            time.sleep(0.03)
            with span("leaf"):
                time.sleep(0.01)
    laps = {lap[0]: lap for lap in bus.spans}
    assert [lap[0] for lap in bus.spans] == ["leaf", "inner", "outer"]
    assert laps["outer"][3] == {"parent": None}
    assert laps["inner"][3] == {"parent": "outer"}
    assert laps["leaf"][3] == {"parent": "inner"}
    got = reduce_laps(list(bus.spans))
    n, total, own, _ = got["rows"][("outer", None)]
    _, inner_total, inner_own, _ = got["rows"][("inner", "outer")]
    _, leaf_total, leaf_own, _ = got["rows"][("leaf", "inner")]
    assert n == 1 and total >= 0.06
    # a span's self time is its duration less what its children cover
    assert own == pytest.approx(total - inner_total, abs=1e-5)
    assert inner_own == pytest.approx(inner_total - leaf_total, abs=1e-5)
    assert leaf_own == pytest.approx(leaf_total) and leaf_own >= 0.01
    assert got["top_s"] == pytest.approx(total)


def test_a_span_entered_twice_sums(bus, reduce_laps):
    for rows in (3, 4):
        with span("setup.tables", table="t", sub_rows=rows):
            time.sleep(0.005)
    got = reduce_laps(list(bus.spans))
    n, total, own, counters = got["rows"][("setup.tables", None)]
    assert n == 2 and total >= 0.01 and own == pytest.approx(total)
    assert counters == {"sub_rows": 7}
    assert got["labels"][("setup.tables", "t")][0] == 2


def test_a_counter_added_inside_the_body_is_in_the_batch(bus):
    with span("setup.upload", what="tables") as s:
        s["h2d_bytes"] += 100          # a counter not given yet reads 0
        s["h2d_bytes"] += 28
        s["edges"] = 5
    assert s.ms is not None and s.ms >= 0
    rec = flush_spans("setup")
    (name, mono0, ms, args), = rec["spans"]
    assert (name, args) == ("setup.upload", {
        "parent": None, "what": "tables", "h2d_bytes": 128, "edges": 5})
    assert ms == round(s.ms, 3) and mono0 <= rec["mono"]


def test_an_exception_still_records_and_pops(bus):
    with span("outer"):
        with pytest.raises(KeyError):
            with span("fails"):
                raise KeyError("x")
        with span("after"):
            pass
    assert [(lap[0], lap[3]["parent"]) for lap in bus.spans] == [
        ("fails", "outer"), ("after", "outer"), ("outer", None)]
    with span("next"):                 # the stack is empty again
        pass
    assert bus.spans[-1][3] == {"parent": None}


def test_flush_emits_one_event_and_empties_the_buffer(bus, tmp_path):
    path = str(tmp_path / "ev.jsonl")
    bus.add_sink(events.JsonlSink(path))
    with span("a"):
        with span("b"):
            pass
    rec = flush_spans("setup")
    assert len(bus.spans) == 0
    assert flush_spans("setup") is None      # nothing buffered: no event
    bus.close()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) == 1 and len(_batches(recs)) == 1
    assert recs[0]["msg"] == "spans: 2 laps (setup)"
    assert [lap[0] for lap in recs[0]["spans"]] == ["b", "a"]
    assert "console" not in recs[0] and rec["console"] is False
    assert list(bus.ring)[-1] is rec         # and in the flight ring


def test_threads_nest_apart_and_lose_no_lap(bus):
    """More workers than cores, a short switch interval: every lap lands
    in the buffer once, under its own thread's enclosing span."""
    workers, laps = 16, 100
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work(i):
        with span(f"outer{i}"):
            for _ in range(laps):
                with span("inner", worker=i):
                    pass

    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    got = list(bus.spans)
    assert len(got) == workers * (laps + 1)
    assert all(args["parent"] == f"outer{args['worker']}"
               for name, _, _, args in got if name == "inner")


def test_ten_thousand_empty_spans_cost_microseconds(bus):
    t0 = time.perf_counter()
    for _ in range(10_000):
        with span("empty"):
            pass
    per_span = (time.perf_counter() - t0) / 10_000
    assert len(bus.spans) == events.SPAN_BUFFER_LAPS    # bounded
    assert per_span < 200e-6        # a set-up enters a few dozen


# ------------------------------------------------- a trainer's own batch

def _tiny():
    import roc_tpu as rt
    from roc_tpu.core.graph import synthetic_dataset
    ds = synthetic_dataset(64, 4, in_dim=8, num_classes=3, seed=0)
    return rt.build_gcn([8, 8, 3], dropout_rate=0.5), ds


def test_each_trainer_flushes_a_batch_of_its_own(bus):
    from roc_tpu.train.trainer import TrainConfig, Trainer
    model, ds = _tiny()
    Trainer(model, ds, TrainConfig(verbose=False, aggr_impl="ell"))
    Trainer(model, ds, TrainConfig(verbose=False, aggr_impl="ell",
                                   symmetric=True))
    first, second = _batches(bus.ring)
    assert len(bus.spans) == 0
    names = [{lap[0] for lap in b["spans"]} for b in (first, second)]
    want = {"setup.resolve", "setup.upload", "setup.params",
            "setup.tables", "setup.steps", "setup.manifest"}
    assert want <= names[0] and want <= names[1]
    # the check runs only where the config does not say
    assert "setup.symmetry" in names[0]
    assert "setup.symmetry" not in names[1]
    assert len(first["spans"]) == len(second["spans"]) + 1
    # the batch goes out after the manifest, whose place is unchanged
    cats = [r["cat"] for r in bus.ring]
    assert cats.index("manifest") < cats.index("timeline")


# ------------------------------------------------------ through cli.main

TOP_LEVEL = {"setup.load", "setup.typed", "setup.reorder", "setup.resolve",
             "setup.symmetry", "setup.partition", "setup.tables",
             "setup.upload", "setup.params", "setup.steps",
             "setup.manifest"}
ALWAYS = {"setup.load", "setup.resolve", "setup.tables", "setup.upload",
          "setup.params", "setup.steps", "setup.manifest"}
PATHS = {
    "gcn-sectioned": (["--model", "gcn", "--impl", "sectioned"],
                      ALWAYS | {"setup.symmetry"}, {"sectioned"}),
    "gcn-flat_sum": (["--model", "gcn", "--impl", "flat_sum"],
                     ALWAYS | {"setup.symmetry"}, {"flat_sum"}),
    "gat": (["--model", "gat", "--heads", "2"],
            ALWAYS | {"setup.symmetry"}, {"ell"}),
    "gcn-reorder": (["--model", "gcn", "--impl", "ell", "--reorder",
                     "bfs"],
                    ALWAYS | {"setup.symmetry", "setup.reorder"}, {"ell"}),
    # a typed graph's backward passes have tables of their own: its
    # union's symmetry is never read
    "rgcn": (["--model", "rgcn", "--node-types", "200,200,112",
              "--embed-types", "1,2"], ALWAYS | {"setup.typed"},
             {"rel.whole.gf_fwd", "rel.whole.gf_bwd", "rel.cut.restrict",
              "rel.cut.gf_fwd", "rel.cut.gf_bwd"}),
    "gcn-parts2": (["--model", "gcn", "--impl", "flat_sum", "--parts",
                    "2"],
                   ALWAYS | {"setup.symmetry", "setup.partition"},
                   {"flat_sum", "padded_rows", "edge_list"}),
}


def _sha(step):
    return hashlib.sha256(step._lowered.as_text().encode()).hexdigest()


def _cli(flags, events_path=None):
    """One run through ``cli.main`` (an epoch and an eval, so both
    programs lower): its set-up batch, the wall around the call up to
    the hand-over, and the two programs' hashes."""
    from roc_tpu.train import cli
    argv = ["--cpu", "--no-compile-cache", "-layers", "16-8-4", "-e", "1",
            "--eval-every", "1", *flags]
    if events_path:
        argv += ["--events", events_path]
    seen = {}

    def inspect(tr):
        seen["sha"] = (_sha(tr._train_step), _sha(tr._eval_step))

    t0 = time.monotonic()
    assert cli.main(argv, inspect=inspect) == 0
    seen["wall_s"] = time.monotonic() - t0
    if events_path:
        events.get_bus().close()
        with open(events_path) as f:
            records = [json.loads(line) for line in f]
    else:
        records = list(events.get_bus().ring)
    seen["batch"], = _batches(records)
    return seen


@pytest.mark.parametrize("path", sorted(PATHS))
def test_cli_spans_of_each_build_path(bus, tmp_path, path):
    flags, want, tables = PATHS[path]
    ev = str(tmp_path / "events.jsonl")
    with_sink = _cli(flags, ev)
    laps = with_sink["batch"]["spans"]
    top = [lap for lap in laps if lap[3]["parent"] is None]
    assert {lap[0] for lap in top} == want
    assert {lap[0] for lap in top} <= TOP_LEVEL
    assert all(lap[0].startswith(lap[3]["parent"] + ".")
               for lap in laps if lap[3]["parent"] is not None)
    # the spans fit inside the wall around cli.main, end to end
    assert sum(lap[2] for lap in top) / 1e3 <= with_sink["wall_s"]
    built = {lap[3].get("table") for lap in laps
             if lap[0] == "setup.tables"}
    assert tables <= built
    uploads = [lap[3] for lap in laps if lap[0] == "setup.upload"]
    assert {"features", "labels", "mask", "tables"} <= {
        a["what"] for a in uploads}
    assert all(a["h2d_bytes"] > 0 for a in uploads)
    params, = [lap[3] for lap in laps if lap[0] == "setup.params"]
    assert params["param_bytes"] > 0
    load, = [lap[3] for lap in laps if lap[0] == "setup.load"]
    assert (load["nodes"], load["edges"] > 0) == (512, True)
    # without a sink the batch reaches the ring only, and the programs
    # the run lowers are the same ones
    events.configure(console=False)
    os.environ.pop("ROC_TPU_EVENTS", None)
    without = _cli(flags)
    assert without["sha"] == with_sink["sha"]
    assert [lap[0] for lap in without["batch"]["spans"]] == [
        lap[0] for lap in laps]


def test_load_dataset_names_the_four_files(bus, tmp_path):
    from roc_tpu.core.graph import (load_dataset, save_dataset,
                                    synthetic_dataset)
    prefix = str(tmp_path / "toy")
    save_dataset(synthetic_dataset(64, 4, in_dim=8, num_classes=3, seed=0),
                 prefix)
    with span("setup.load"):
        load_dataset(prefix, in_dim=8, num_classes=3)
    laps = {lap[0]: lap[3] for lap in bus.spans}
    sizes = {"setup.load.graph": ".add_self_edge.lux",
             "setup.load.features": ".feats.bin",
             "setup.load.labels": ".label", "setup.load.mask": ".mask"}
    for name, suffix in sizes.items():
        assert laps[name] == {
            "parent": "setup.load",
            "file_bytes": os.path.getsize(prefix + suffix)}


def test_timeline_merges_a_setup_batch_without_dropping_a_lap(bus,
                                                              tmp_path):
    ev = str(tmp_path / "events.jsonl")
    laps = _cli(PATHS["gcn-flat_sum"][0], ev)["batch"]["spans"]
    assert all(len(lap) == 4 for lap in laps)
    out = str(tmp_path / "trace.json")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.timeline", ev, "-o", out],
        capture_output=True, text=True, cwd=_REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        drawn = [e for e in json.load(f)["traceEvents"]
                 if e.get("ph") == "X" and e["name"].startswith("setup.")]
    assert sorted(e["name"] for e in drawn) == sorted(
        lap[0] for lap in laps)
    assert all(e["tid"] == 0 for e in drawn)        # the phases lane
    upload = [e for e in drawn if e["name"] == "setup.upload"]
    assert all(e["args"]["h2d_bytes"] > 0 for e in upload)

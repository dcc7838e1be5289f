"""Observability subsystem (roc_tpu/obs): event bus, run manifest,
compile observer, stall heartbeats, report CLI, and the stdout-print
lint ratchet."""

import json
import os
import subprocess
import sys
import time

import pytest

from roc_tpu.obs.events import ConsoleSink, EventLog, JsonlSink
from roc_tpu.obs.heartbeat import Heartbeat

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------- event bus

def test_jsonl_event_roundtrip(tmp_path):
    p = str(tmp_path / "events.jsonl")
    bus = EventLog([JsonlSink(p)])
    bus.emit("resolve", "picked sectioned", requested="auto",
             resolved="sectioned")
    bus.emit("epoch", "epoch 5", console=False, epoch=5,
             epoch_ms=12.5)
    bus.close()
    recs = [json.loads(line) for line in open(p)]
    assert [r["cat"] for r in recs] == ["resolve", "epoch"]
    assert recs[0]["resolved"] == "sectioned"
    assert recs[1]["epoch_ms"] == 12.5
    # the console gate is sink routing, not payload
    assert "console" not in recs[1]
    assert all("t" in r and "msg" in r for r in recs)


def test_console_sink_preserves_hash_prefix(capsys):
    bus = EventLog([ConsoleSink()])
    bus.emit("plan", "memory plan: halo=gather")
    bus.emit("plan", "hidden", console=False)
    err = capsys.readouterr().err
    assert "# memory plan: halo=gather" in err
    assert "hidden" not in err


def test_sink_failure_never_raises(capsys):
    class Boom:
        def write(self, rec):
            raise RuntimeError("disk full")

        def close(self):
            pass

    bus = EventLog([Boom()])
    bus.emit("run", "a")  # must not raise
    bus.emit("run", "b")
    assert "sink" in capsys.readouterr().err  # one-time note


def test_jsonable_fields_degrade_to_str(tmp_path):
    import numpy as np
    p = str(tmp_path / "e.jsonl")
    bus = EventLog([JsonlSink(p)])
    bus.emit("plan", "x", arr=np.arange(3), big=np.int64(7),
             obj=object())
    bus.close()
    rec = json.loads(open(p).read())
    assert rec["arr"] == [0, 1, 2]
    assert rec["big"] == 7
    assert isinstance(rec["obj"], str)


# ------------------------------------------------------- run manifest

def test_run_manifest_schema(tmp_path):
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.obs.events import configure
    from roc_tpu.obs.manifest import run_manifest
    from roc_tpu.train.trainer import TrainConfig
    p = str(tmp_path / "ev.jsonl")
    try:
        configure(jsonl_path=p, console=False)
        ds = synthetic_dataset(64, 4, in_dim=8, num_classes=3, seed=0)
        fields = run_manifest(config=TrainConfig(aggr_impl="ell"),
                              dataset=ds,
                              model=build_gcn([8, 8, 3]),
                              console=False)
    finally:
        configure(jsonl_path=None)
    rec = json.loads(open(p).read())
    assert rec["cat"] == "manifest"
    for key in ("jax_version", "platform", "device_count", "config",
                "resolved", "dataset", "model"):
        assert key in rec, key
    assert rec["resolved"]["aggr_impl"] == "ell"
    assert rec["dataset"]["num_nodes"] == 64
    assert rec["config"]["aggr_impl"] == "ell"
    # dtypes serialize by dtype NAME
    assert rec["config"]["dtype"] == "float32"
    assert fields["dataset"]["num_edges"] == ds.graph.num_edges


@pytest.mark.parametrize("impl,parts", [("sectioned", 1),
                                        ("flat_sum", 1),
                                        ("flat_sum", 2), ("ell", 1)])
def test_manifest_resolved_carries_agg_window(tmp_path, impl, parts):
    """``resolved`` (what the benchmark's ``plan`` line prints) says
    how far the windowed chunk scan engaged: window rows per section
    and the carry's height — straight from the trainer's own tables;
    nothing for a layout that scans no carry."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.obs.events import configure
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig, Trainer
    p = str(tmp_path / "ev.jsonl")
    ds = synthetic_dataset(300, 5, in_dim=8, num_classes=3, seed=1)
    cfg = TrainConfig(aggr_impl=impl, verbose=False, symmetric=True)
    try:
        configure(jsonl_path=p, console=False)
        if parts > 1:
            tr = DistributedTrainer(build_gcn([8, 8, 3]), ds, parts, cfg)
            rows, gctx = tr.pg.part_nodes, tr._gctx()
        else:
            tr = Trainer(build_gcn([8, 8, 3]), ds, cfg)
            rows, gctx = ds.graph.num_nodes, tr.gctx
    finally:
        configure(jsonl_path=None)
    res = [json.loads(line) for line in open(p)
           if json.loads(line)["cat"] == "manifest"][-1]["resolved"]
    assert res["aggr_impl"] == impl
    if impl == "ell":
        assert res["agg_window_rows"] == []
        assert res["agg_carry_rows"] is None
        return
    from roc_tpu.ops.aggregate import scan_window_rows
    carry = rows + 1
    assert res["agg_carry_rows"] == carry
    wins = res["agg_window_rows"]
    table = ([m[2] for m in gctx.sect_meta] if impl == "sectioned"
             else [gctx.flat8_win])
    assert table and all(w > 0 and w % 128 == 0 for w in table)
    # what a chunk step really reads and writes, not the raw span
    assert wins == [scan_window_rows(w, carry) for w in table]


def test_git_sha_resolves_here():
    from roc_tpu.obs.manifest import git_sha
    sha = git_sha()
    assert sha is None or (len(sha) == 40
                           and all(c in "0123456789abcdef" for c in sha))


# ---------------------------------------------------------- heartbeat

def test_heartbeat_fire_and_cancel(tmp_path):
    p = str(tmp_path / "hb.jsonl")
    bus = EventLog([JsonlSink(p)])
    with Heartbeat("claiming backend", interval_s=0.05, bus=bus) as hb:
        time.sleep(0.22)
    fired_at_exit = hb.fired
    assert fired_at_exit >= 2
    time.sleep(0.15)  # canceled: no further beats
    assert hb.fired == fired_at_exit
    recs = [json.loads(line) for line in open(p)]
    assert all(r["cat"] == "stall" for r in recs)
    assert all(r["stage"] == "claiming backend" for r in recs)
    assert "still waiting in claiming backend" in recs[0]["msg"]
    assert recs[-1]["elapsed_s"] >= recs[0]["elapsed_s"]


def test_heartbeat_fast_region_emits_nothing(tmp_path):
    p = str(tmp_path / "hb.jsonl")
    bus = EventLog([JsonlSink(p)])
    with Heartbeat("quick", interval_s=5.0, bus=bus) as hb:
        pass
    assert hb.fired == 0
    assert not os.path.exists(p)  # lazy sink never opened


def test_heartbeat_zero_interval_is_disabled(tmp_path):
    """ROC_TPU_HEARTBEAT_S=0 is the off switch — no watchdog thread,
    never a zero-wait spin flooding the artifact."""
    p = str(tmp_path / "hb.jsonl")
    bus = EventLog([JsonlSink(p)])
    with Heartbeat("off", interval_s=0, bus=bus) as hb:
        time.sleep(0.05)
    assert hb.fired == 0 and hb._thread is None
    assert not os.path.exists(p)


# ----------------------------------------------------- compile observer

def test_cost_and_memory_summary_degrade_gracefully():
    from roc_tpu.obs.compile_watch import cost_summary, memory_summary

    class NoIntrospection:
        def cost_analysis(self):
            raise NotImplementedError("backend says no")

        def memory_analysis(self):
            return None

    c = cost_summary(NoIntrospection())
    assert c == {"flops": None, "bytes_accessed": None}
    m = memory_summary(NoIntrospection())
    assert m["peak_bytes"] is None


def test_observed_jit_degrades_to_plain_call(tmp_path):
    """A wrapped callable without the AOT surface must still execute
    (one degradation event, then plain calls)."""
    from roc_tpu.obs.compile_watch import ObservedJit
    calls = []

    def plain(x):
        calls.append(x)
        return x + 1

    oj = ObservedJit(jitfn=plain, name="stub")
    assert oj(1) == 2 and oj(2) == 3
    assert calls == [1, 2]
    assert oj._degraded and oj.cost is None


def test_observed_jit_captures_cost_and_model_delta():
    import jax.numpy as jnp
    from roc_tpu.obs.compile_watch import ObservedJit

    oj = ObservedJit(lambda x: (x @ x).sum(), name="mm",
                     modeled_bytes=1)
    x = jnp.ones((32, 32))
    assert float(oj(x)) == float((x @ x).sum())
    assert oj.cost is not None
    assert oj.cost["flops"] and oj.cost["flops"] > 0
    assert oj.cost["compile_s"] >= 0
    # CPU exposes memory_analysis -> the modeled-vs-actual delta exists
    assert oj.cost["peak_bytes"] is not None
    assert oj.cost["model_delta_bytes"] == oj.cost["peak_bytes"] - 1
    # steady-state path reuses the compiled executable
    assert oj._compiled is not None
    assert float(oj(x + 1)) > 0


def test_peak_flops_table():
    from roc_tpu.obs.compile_watch import peak_flops_per_s
    assert peak_flops_per_s("TPU v5 lite") == 197e12
    assert peak_flops_per_s("TPU v4") == 275e12
    assert peak_flops_per_s("cpu") is None
    assert peak_flops_per_s() is None          # the CPU rig itself
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops_per_s("TPU v9")


# --------------------------------------------- end-to-end through CLI

def test_cli_events_jsonl_and_report(tmp_path):
    """The acceptance path: a CPU CLI run with --events produces a
    manifest, a compile event with flops/peak-HBM/modeled-delta, and
    per-phase epoch spans; `python -m roc_tpu.report` renders it."""
    from roc_tpu.obs.events import configure
    from roc_tpu.train import cli
    ev = str(tmp_path / "events.jsonl")
    old_env = os.environ.get("ROC_TPU_EVENTS")
    try:
        rc = cli.main(["--cpu", "--no-compile-cache", "-e", "4",
                       "-layers", "8-8-3", "--impl", "ell",
                       "--eval-every", "2", "--events", ev])
    finally:
        configure(jsonl_path=None)
        if old_env is None:
            os.environ.pop("ROC_TPU_EVENTS", None)
        else:
            os.environ["ROC_TPU_EVENTS"] = old_env
    assert rc == 0
    recs = [json.loads(line) for line in open(ev)]
    cats = {r["cat"] for r in recs}
    assert {"manifest", "compile", "epoch", "run"} <= cats
    comp = [r for r in recs if r["cat"] == "compile"
            and r.get("name") == "train_step"]
    assert comp, recs
    assert comp[0]["flops"] > 0
    assert comp[0]["peak_bytes"] > 0
    assert comp[0]["modeled_bytes"] > 0
    assert comp[0]["model_delta_bytes"] == \
        comp[0]["peak_bytes"] - comp[0]["modeled_bytes"]
    spans = [r for r in recs if r["cat"] == "epoch" and r.get("spans")]
    assert spans and {"compile", "train", "eval"} <= \
        set(spans[-1]["spans"])
    ep = [r for r in recs if r["cat"] == "epoch" and "epoch_ms" in r]
    assert ep and ep[0]["edges_per_s"] > 0

    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.report", ev],
        capture_output=True, text=True, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert r.returncode == 0, r.stderr
    for needle in ("run manifest", "compile", "train_step",
                   "phase spans", "edges_per_s"):
        assert needle in r.stdout, (needle, r.stdout)


# ------------------------------------------------------- lint ratchet

def test_lint_prints_ratchet_passes():
    """scripts/lint_prints.sh: the event-log migration cannot regress
    — a bare stdout print() in roc_tpu/ fails the tier."""
    r = subprocess.run(
        ["sh", os.path.join(_REPO, "scripts", "lint_prints.sh")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


def test_lint_prints_catches_stdout_leak(tmp_path):
    """The ratchet actually bites: a planted bare print() is caught."""
    import shutil
    victim = os.path.join(_REPO, "roc_tpu", "obs", "__init__.py")
    planted = tmp_path / "repo"
    (planted / "scripts").mkdir(parents=True)
    shutil.copy(os.path.join(_REPO, "scripts", "lint_prints.sh"),
                planted / "scripts" / "lint_prints.sh")
    dst = planted / "roc_tpu"
    dst.mkdir()
    (dst / "leaky.py").write_text("print('oops stdout')\n")
    # the planted tree has no roc_tpu.analysis package — the thin
    # wrapper imports the linter from the real checkout via PYTHONPATH
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(["sh", str(planted / "scripts" /
                                  "lint_prints.sh")],
                       capture_output=True, text=True, timeout=60,
                       env=env)
    assert r.returncode == 1
    assert "leaky.py:1" in r.stdout
    assert os.path.exists(victim)  # the real tree untouched

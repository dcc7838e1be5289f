"""Fleet observability (PR 17): the streaming metrics registry, the
SLO engine's multi-window burn-rate alerting, the ``report --slo``
dashboard, and THE acceptance drill — a 10x load spike against a
2-replica Router breaches a declared objective, fires a dated ``slo``
breach event with a flight-record dump, renders in ``report --slo``,
and recovers to a green machine-readable ``Router.health()`` once the
spike rolls out of the compliance window.

Unit layers run on an injected fake clock (no sleeps); the drill runs
through the REAL export → cold-load → subprocess-replica path."""

import glob
import json
import os
import time

import numpy as np
import pytest

from roc_tpu.obs.metrics_registry import MetricsRegistry
from roc_tpu.obs.slo import BURN_RULES, Slo, SloEngine, parse_slo

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_reg(name="t", t0=1000.0):
    clk = [t0]
    return clk, MetricsRegistry(name, now=lambda: clk[0])


# ------------------------------------------------- registry primitives

def test_counter_windowed_sums():
    clk, reg = _fake_reg()
    c = reg.counter("requests")
    c.inc(5)
    clk[0] += 30.0
    c.inc(2)
    assert c.total == 7                  # lifetime: an attribute
    assert c.sum_over(10.0) == 2         # trailing window
    assert c.sum_over(60.0) == 7
    assert c.rate(10.0) == pytest.approx(0.2)
    clk[0] += 300.0                      # everything expires
    assert c.sum_over(60.0) == 0
    assert c.total == 7                  # lifetime survives the ring
    snap = c.snapshot((10.0,))
    assert snap["kind"] == "counter" and snap["total"] == 7
    assert snap["sum_10s"] == 0


def test_counter_get_or_create_idempotent():
    clk, reg = _fake_reg()
    a = reg.counter("x")
    a.inc(3)
    assert reg.counter("x") is a
    assert reg.counter("x").total == 3


def test_histogram_quantiles_windowed():
    clk, reg = _fake_reg()
    h = reg.histogram("request_ms")
    for v in [1.0] * 90 + [100.0] * 10:
        h.record(v)
    # log-bucket midpoints: within one bucket (~16% relative) of exact
    assert h.quantile(0.50, 60.0) == pytest.approx(1.0, rel=0.2)
    assert h.quantile(0.99, 60.0) == pytest.approx(100.0, rel=0.2)
    assert h.frac_above(10.0, 60.0) == pytest.approx(0.10)
    assert h.count_over(60.0) == 100
    clk[0] += 30.0
    h.record(5.0)
    # the 10 s window only sees the new sample
    assert h.count_over(10.0) == 1
    assert h.quantile(0.5, 10.0) == pytest.approx(5.0, rel=0.2)
    # lifetime view keeps everything
    assert h.count_over(None) == 101
    assert h.quantile(0.99, None) == pytest.approx(100.0, rel=0.2)
    snap = h.snapshot((10.0,))
    assert snap["kind"] == "histogram"
    assert snap["n_10s"] == 1 and snap["total"] == 101
    # empty window: honest None, not 0
    clk[0] += 300.0
    assert h.quantile(0.99, 10.0) is None
    assert h.frac_above(10.0, 10.0) == 0.0


def test_gauge_value_and_ewma():
    _, reg = _fake_reg()
    g = reg.gauge("step_ewma_ms", ewma_alpha=0.5)
    assert g.value is None and g.ewma is None
    g.set(100.0)
    g.set(200.0)
    assert g.value == 200.0
    assert g.ewma == pytest.approx(150.0)
    plain = reg.gauge("ratio")
    plain.set(1.5)
    assert plain.ewma == 1.5             # no alpha: ewma == value


def test_registry_snapshot_and_dump(tmp_path):
    clk, reg = _fake_reg("router")
    reg.counter("ok").inc(9)
    reg.histogram("request_ms").record(2.0)
    reg.gauge("ratio").set(1.1)
    doc = reg.snapshot(windows=(10.0, 60.0))
    assert doc["registry"] == "router"
    assert doc["windows_s"] == [10.0, 60.0]
    assert doc["metrics"]["ok"]["sum_10s"] == 9
    p = str(tmp_path / "snap.json")
    reg.dump(p, windows=(10.0,), extra={"component": "router"})
    loaded = json.load(open(p))
    assert loaded["component"] == "router"
    assert loaded["metrics"]["ok"]["total"] == 9
    assert "t" in loaded                 # wall stamp for the watcher


# ------------------------------------------------------- SLO grammar

def test_parse_slo_availability_roundtrip():
    s = parse_slo("availability(ok/requests) >= 0.999 over 60s")
    assert s.kind == "availability"
    assert (s.ok, s.total) == ("ok", "requests")
    assert s.target == 0.999 and s.window_s == 60.0
    assert s.budget == pytest.approx(0.001)
    assert parse_slo(s.spec()).spec() == s.spec()


def test_parse_slo_latency_named():
    s = parse_slo("lat99: p99(request_ms) <= 50ms over 30s")
    assert s.name == "lat99" and s.kind == "latency"
    assert s.hist == "request_ms"
    assert s.q == 0.99 and s.limit_ms == 50.0
    assert s.budget == pytest.approx(0.01)
    assert parse_slo(s.spec()).spec() == s.spec()


def test_parse_slo_rejects_garbage_and_zero_budget():
    with pytest.raises(ValueError):
        parse_slo("p99 of latency under 50")
    with pytest.raises(ValueError):
        parse_slo("availability(ok/requests) >= 1.0 over 60s")
    with pytest.raises(ValueError):
        Slo("x", "throughput", 60.0, 0.9)


# ---------------------------------------------- burn-rate engine (fake clock)

def _engine(clk, reg, specs, **kw):
    kw.setdefault("flight_record", False)
    kw.setdefault("warmup_s", 0.0)
    return SloEngine(reg, specs, component="test",
                     now=lambda: clk[0], **kw)


def test_burn_rate_breach_and_recovery_edges():
    """The full transition arc on a fake clock: healthy traffic is
    green; a bad burst fires the burn-rate rules exactly once
    (edge-triggered); recovery waits for BOTH rules quiet AND window
    compliance, then emits exactly one recovered transition."""
    clk, reg = _fake_reg()
    eng = _engine(clk, reg,
                  ["availability(ok/requests) >= 0.9 over 60s"])
    req, ok = reg.counter("requests"), reg.counter("ok")
    req.inc(100), ok.inc(100)
    v = eng.evaluate()
    assert v["ok"] is True
    assert v["states"]["availability_60s"] == "ok"
    # 10x spike, 90% of it failing: bad_frac 0.9 / budget 0.1 = 9x
    # burn >= the slow rule's 6x on both its windows
    clk[0] += 1.0
    req.inc(1000), ok.inc(100)
    v = eng.evaluate()
    ob = v["objectives"][0]
    assert ob["firing"] is True
    assert ob["burn"] >= 6.0
    assert v["states"]["availability_60s"] == "breach"
    assert v["ok"] is False
    # still firing: NO second transition (edge-triggered)
    v2 = eng.evaluate()
    assert v2["states"]["availability_60s"] == "breach"
    # burst expires from every window -> quiet AND compliant
    clk[0] += 130.0
    req.inc(50), ok.inc(50)
    v3 = eng.evaluate()
    assert v3["states"]["availability_60s"] == "ok"
    assert v3["ok"] is True


def test_latency_objective_burns_on_slow_tail():
    clk, reg = _fake_reg()
    eng = _engine(clk, reg, ["p95(request_ms) <= 10ms over 60s"])
    h = reg.histogram("request_ms")
    for _ in range(100):
        h.record(2.0)
    assert eng.evaluate()["ok"] is True
    # half the traffic above the limit: bad 0.5 / budget 0.05 = 10x
    for _ in range(100):
        h.record(50.0)
    v = eng.evaluate()
    assert v["states"]["p95_request_ms"] == "breach"
    assert v["objectives"][0]["value"] == pytest.approx(50.0, rel=0.2)


def test_warmup_suppresses_startup_false_positive():
    """Availability counts a request at submit and its ok only at
    completion — the first evaluations after traffic starts see
    bad_frac ~ 1 over a tiny sample.  The warmup guard keeps rules
    from firing until traffic has flowed for warmup_s."""
    clk, reg = _fake_reg()
    eng = _engine(clk, reg,
                  ["availability(ok/requests) >= 0.9 over 60s"],
                  warmup_s=2.0)
    req, ok = reg.counter("requests"), reg.counter("ok")
    req.inc(20)                          # submitted, none complete yet
    v = eng.evaluate()
    assert v["states"]["availability_60s"] == "ok"
    assert v["objectives"][0].get("warmup") is True
    # completions land; past warmup the same traffic is green
    ok.inc(20)
    clk[0] += 3.0
    assert eng.evaluate()["states"]["availability_60s"] == "ok"
    # and a GENUINE post-warmup burst still fires
    req.inc(1000), ok.inc(100)
    assert eng.evaluate()["states"]["availability_60s"] == "breach"


def test_breach_emits_dated_event_and_flight_record(tmp_path,
                                                    monkeypatch):
    """The alert surface: entering breach emits one dated ``slo``
    event on the bus and dumps the PR-9 flight record; recovery emits
    the matching ``recovered`` event."""
    from roc_tpu.obs import events
    ev_path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("ROC_TPU_FLIGHT_DIR", str(tmp_path))
    events.configure(jsonl_path=ev_path)
    try:
        clk, reg = _fake_reg()
        eng = _engine(clk, reg,
                      ["availability(ok/requests) >= 0.9 over 60s"],
                      flight_record=True)
        req, ok = reg.counter("requests"), reg.counter("ok")
        req.inc(1000), ok.inc(50)
        eng.evaluate()
        clk[0] += 130.0
        req.inc(50), ok.inc(50)
        eng.evaluate()
    finally:
        events.configure(jsonl_path=None)
    recs = [json.loads(ln) for ln in open(ev_path) if ln.strip()]
    slo = [r for r in recs if r.get("cat") == "slo"]
    assert [r["kind"] for r in slo] == ["breach", "recovered"]
    br = slo[0]
    assert br["slo"] == "availability_60s"
    assert br["component"] == "test"
    assert br["burn"] >= 6.0
    assert isinstance(br["t"], float)    # dated: wall-clock stamped
    dumps = glob.glob(str(tmp_path / "flightrecord_*slo-breach*"))
    assert len(dumps) == 1


def test_tick_rate_limits_and_caches():
    clk, reg = _fake_reg()
    eng = _engine(clk, reg,
                  ["availability(ok/requests) >= 0.9 over 60s"],
                  eval_interval_s=0.25)
    reg.counter("requests").inc(10), reg.counter("ok").inc(10)
    v1 = eng.tick()
    assert v1 is not None and v1["ok"] is True
    assert eng.tick() is v1              # within interval: cached
    clk[0] += 0.3
    assert eng.tick() is not v1          # fresh evaluation


# -------------------------------------------------- report --slo golden

def test_report_slo_dashboard_golden(tmp_path, capsys):
    """``python -m roc_tpu.report --slo snap.json`` renders the
    snapshot as the watch-able dashboard: health verdict, objectives
    table, counters/gauges/histograms with their windowed views."""
    from roc_tpu import report
    clk, reg = _fake_reg("router")
    reg.counter("requests").inc(120)
    reg.counter("ok").inc(119)
    h = reg.histogram("request_ms")
    for v in [2.0] * 99 + [40.0]:
        h.record(v)
    reg.gauge("inflight").set(3)
    eng = _engine(clk, reg,
                  ["availability(ok/requests) >= 0.99 over 60s",
                   "lat99: p99(request_ms) <= 50ms over 60s"])
    snap = str(tmp_path / "snap.json")
    reg.dump(snap, windows=(10.0, 60.0),
             extra={"component": "router",
                    "health": {**eng.evaluate(),
                               "replicas_alive": 2, "replicas": 2}})
    rc = report.main(["--slo", snap])
    out = capsys.readouterr().out
    assert rc == 0
    assert "slo dashboard" in out and "component=router" in out
    assert "health: OK" in out and "(2/2 replicas alive)" in out
    assert "availability_60s" in out and "lat99" in out
    assert "requests" in out and "request_ms" in out
    assert "inflight" in out
    # a breach snapshot renders BREACH, not a stack trace
    reg.counter("requests").inc(500)
    reg.dump(snap, windows=(10.0,),
             extra={"component": "router",
                    "health": {**eng.evaluate(),
                               "replicas_alive": 1, "replicas": 2}})
    rc = report.main(["--slo", snap])
    out = capsys.readouterr().out
    assert rc == 0 and "health: BREACH" in out


def test_report_slo_requires_input(capsys):
    from roc_tpu import report
    with pytest.raises(SystemExit):
        report.main(["--slo"])           # bare --slo with no events


# ------------------------------------------ the e2e spike drill (subprocess)

@pytest.fixture(scope="module", autouse=True)
def _shed_native_jit_state():
    """Same PR-7/8 mitigation as the other serve modules: shed the
    native JIT state accumulated by the export below."""
    yield
    import jax
    jax.clear_caches()


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    """One exported precomputed artifact + warm persistent cache (the
    replicas cold-load with zero new compiles)."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.sgc import build_sgc
    from roc_tpu.serve.export import build_predictor, export_predictor
    from roc_tpu.train.trainer import TrainConfig
    d = tmp_path_factory.mktemp("slo_art")
    cache = str(d / "cache")
    os.makedirs(cache)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["ROC_TPU_CACHE_MIN_SECS"] = "0"
    ds = synthetic_dataset(num_nodes=300, avg_degree=6, in_dim=24,
                           num_classes=5, seed=0)
    pred = build_predictor(build_sgc([24, 5], k=2, dropout_rate=0.5),
                           ds, TrainConfig(verbose=False,
                                           symmetric=True),
                           backend="precomputed")
    art = str(d / "artifact")
    export_predictor(pred, art,
                     dataset_meta={"V": ds.graph.num_nodes,
                                   "E": int(ds.graph.num_edges)})
    yield art, ds
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)


def test_slo_spike_breach_recovery_e2e(artifact, tmp_path,
                                       monkeypatch):
    """THE PR-17 acceptance drill, through the real export →
    cold-load → subprocess-replica path: a 10x spike of unmeetable-
    deadline requests against a 2-replica Router burns the declared
    availability budget — the engine fires a dated ``slo`` breach
    event with a flight-record dump and ``health()`` goes red; once
    the spike rolls out of the compliance window under quiet
    successful traffic, a ``recovered`` event fires and ``health()``
    returns green with windowed availability 1.0.  The snapshot feed
    + event stream render in ``report --slo``."""
    from roc_tpu.obs import events
    from roc_tpu.serve.errors import ServeTimeout
    from roc_tpu.serve.router import Router
    art, ds = artifact
    ev_path = str(tmp_path / "ev.jsonl")
    snap_path = str(tmp_path / "snap.json")
    monkeypatch.setenv("ROC_TPU_FLIGHT_DIR", str(tmp_path))
    events.configure(jsonl_path=ev_path)
    env = os.environ.copy()
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("ROC_TPU_FAULT", None)
    ids = np.arange(4, dtype=np.int32)
    slo_name = "availability_8s"
    try:
        with Router(art, n_replicas=2, cpu=True, env=env,
                    default_deadline_ms=30_000.0, stats_window_s=8.0,
                    slos=("availability(ok/requests) >= 0.95 "
                          "over 8s",),
                    snapshot_path=snap_path) as router:
            # quiet phase: warm both replicas, pass the engine warmup
            t_end = time.monotonic() + 3.0
            while time.monotonic() < t_end:
                router.submit(ids).result(timeout=60)
                time.sleep(0.05)
            assert router.health()["ok"] is True
            # 10x spike with unmeetable deadlines: every request
            # times out, bad_frac ~ 1 against a 0.05 budget
            spike = [router.submit(ids, deadline_ms=0.2)
                     for _ in range(150)]
            timeouts = 0
            for f in spike:
                try:
                    f.result(timeout=60)
                except ServeTimeout:
                    timeouts += 1
            assert timeouts > 100
            deadline = time.monotonic() + 10.0
            breached = False
            while time.monotonic() < deadline:
                h = router.health()
                if h["states"].get(slo_name) == "breach":
                    breached = True
                    break
                time.sleep(0.2)
            assert breached, h
            assert h["ok"] is False
            # recovery: quiet successful traffic until the spike is
            # outside the 8 s compliance window
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                router.submit(ids).result(timeout=60)
                h = router.health()
                if h["ok"] and h["states"].get(slo_name) == "ok":
                    break
                time.sleep(0.2)
            assert h["ok"] is True, h
            assert h["states"][slo_name] == "ok"
            stats = router.stats()
            assert stats["availability"] == 1.0
            assert stats["window_s"] == 8.0
    finally:
        events.configure(jsonl_path=None)
    # the alert trail: dated breach + recovered slo events
    recs = [json.loads(ln) for ln in open(ev_path) if ln.strip()]
    slo_evs = [r for r in recs if r.get("cat") == "slo"]
    kinds = [r["kind"] for r in slo_evs]
    assert "breach" in kinds and "recovered" in kinds
    assert kinds.index("breach") < kinds.index("recovered")
    br = next(r for r in slo_evs if r["kind"] == "breach")
    assert br["slo"] == slo_name and br["component"] == "router"
    assert isinstance(br["t"], float)
    # flight record dumped at the breach edge
    assert glob.glob(str(tmp_path / "flightrecord_*slo-breach*"))
    # the live snapshot feed exists and report --slo renders both the
    # dashboard and the dated transition table
    assert os.path.exists(snap_path)
    import io
    from roc_tpu import report
    buf_rc = report.main(["--slo", snap_path, ev_path])
    assert buf_rc == 0

"""Serving robustness (ISSUE 13): deadlines, backpressure, versioned
tables, graceful drain, and the replicated-routing fault drills.

The contract under test — an ACCEPTED request completes with a correct
answer or fails with a typed ``serve/errors.py`` exception; never a
hang, never a wrong value:

- deadline'd requests resolve with ``ServeTimeout`` within ~one
  microbatch of their deadline; a saturating burst sheds typed
  ``ServeOverload`` at the bounded admission queue;
- a concurrent ``add_edges`` publish never tears a microbatch: every
  result is bit-exact for the table version it was served under
  (``ServeResult.version``), asserted under a client-thread stress —
  the versioned-swap acceptance criterion;
- ``drain()`` finishes in-flight work and rejects late submits with
  ``ServeClosed``;
- the Router drills run through the REAL export→cold-load→load-gen
  path with replica subprocesses: ``replica_sigkill`` mid-load fails
  over with zero lost/wrong answers and a timeline-visible failover
  marker, ``serve_io`` re-dispatches transparently,
  ``table_swap_mid_query`` finishes the in-flight batch on its
  captured version, ``replica_stall`` is bounded by hedging, and a
  SIGTERM'd replica drains gracefully (exit 0) — the PR-8 preemption
  contract applied to serving.
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from roc_tpu.serve.errors import (ServeClosed, ServeOverload,
                                  ServeTimeout)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dataset(V=300, seed=0):
    from roc_tpu.core.graph import synthetic_dataset
    return synthetic_dataset(num_nodes=V, avg_degree=6, in_dim=24,
                             num_classes=5, seed=seed)


def _sgc_model():
    from roc_tpu.models.sgc import build_sgc
    return build_sgc([24, 5], k=2, dropout_rate=0.5)


def _config(**kw):
    from roc_tpu.train.trainer import TrainConfig
    kw.setdefault("verbose", False)
    kw.setdefault("symmetric", True)
    return TrainConfig(**kw)


@pytest.fixture(scope="module", autouse=True)
def _shed_native_jit_state():
    """The in-process rig + versioned-table stress compile several
    predictor/program variants into the pytest process; shed the
    accumulated native JIT state when the module ends (the PR-7/8
    mitigation for the known jaxlib-0.4.x XLA:CPU corruption flake
    under per-process compile churn — test_flat_sum /
    test_mixed_precision / test_drills carry the same fixture)."""
    yield
    import jax
    jax.clear_caches()


@pytest.fixture(scope="module")
def rig():
    """Predictor + full-table reference logits (fresh Glorot weights —
    robustness behavior is weight-independent)."""
    from roc_tpu.serve.export import build_predictor
    ds = _dataset()
    pred = build_predictor(_sgc_model(), ds, _config(),
                           backend="auto")
    ref = pred.query(np.arange(ds.graph.num_nodes))
    return ds, pred, ref


class _SlowPredictor:
    """Delegating wrapper whose dispatch sleeps — the knob that makes
    queue pressure deterministic on any CI box."""

    def __init__(self, pred, delay_s):
        self._pred = pred
        self.delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self._pred, name)

    def query(self, ids, pub=None):
        time.sleep(self.delay_s)
        return self._pred.query(ids, pub=pub)


# --------------------------------------------- deadlines + backpressure

def test_deadline_returns_typed_timeout_within_budget(rig):
    """Queued requests whose deadline lapses while the dispatcher is
    busy resolve with ServeTimeout at the next microbatch boundary —
    never a hang, and never slower than ~deadline + one microbatch."""
    from roc_tpu.serve.server import Server
    ds, pred, ref = rig
    slow = _SlowPredictor(pred, 0.10)
    deadline_ms = 30.0
    with Server(slow, max_wait_ms=0.0, name="deadline") as srv:
        srv.submit([0])            # occupy the dispatcher ~100 ms
        # wait until that dispatch actually STARTED (otherwise the
        # deadline'd submits below would coalesce into the same first
        # microbatch and complete instead of queueing behind it)
        t_wait = time.monotonic()
        while not srv._dispatching and time.monotonic() - t_wait < 2.0:
            time.sleep(0.002)
        assert srv._dispatching
        futs = [(i, time.monotonic(),
                 srv.submit([i], deadline_ms=deadline_ms))
                for i in range(1, 9)]
        outcomes = []
        for i, t_sub, f in futs:
            try:
                rows = f.result(timeout=10)
                assert np.array_equal(rows, ref[[i]])
                outcomes.append(("ok", time.monotonic() - t_sub))
            except ServeTimeout:
                outcomes.append(("timeout", time.monotonic() - t_sub))
        stats = srv.stats()
    timeouts = [dt for kind, dt in outcomes if kind == "timeout"]
    assert timeouts, outcomes
    # budget: deadline + one microbatch (the 100 ms sleep) + sched
    # slack — generous for a loaded CI box, but a HANG (the 10 s
    # result timeout) can never pass
    budget_s = deadline_ms / 1e3 + slow.delay_s + 1.0
    assert max(timeouts) <= budget_s, outcomes
    assert stats["n_timeout"] == len(timeouts)
    assert stats["error_rate"] > 0


def test_saturating_burst_sheds_typed_overload(rig):
    """Past the bounded admission queue, submit() sheds immediately
    with ServeOverload; accepted requests still answer correctly and
    the shed rate shows in stats()."""
    from roc_tpu.serve.server import Server
    ds, pred, ref = rig
    slow = _SlowPredictor(pred, 0.05)
    with Server(slow, max_wait_ms=0.0, max_queue=4,
                name="overload") as srv:
        futs = [srv.submit([i % 50]) for i in range(60)]
        ok = shed = 0
        for i, f in enumerate(futs):
            try:
                rows = f.result(timeout=30)
                assert np.array_equal(rows, ref[[i % 50]])
                ok += 1
            except ServeOverload:
                shed += 1
        stats = srv.stats()
    assert ok + shed == 60
    assert shed > 0 and ok > 0
    assert stats["n_shed"] == shed
    # stats rounds rates to 4 decimals
    assert stats["shed_rate"] == pytest.approx(shed / 60, abs=1e-4)


# ------------------------------------------------------ versioned swap

def test_versioned_swap_concurrent_stress(rig):
    """THE versioned-table acceptance: client threads hammer the
    server while the control plane publishes two add_edges swaps.
    Every result must be bit-exact for the version stamped on it
    (``ServeResult.version``) — a torn batch (rows from two versions)
    or a value drifting from its version's table is a failure."""
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.server import Server
    ds = _dataset(seed=3)
    pred = build_predictor(_sgc_model(), ds, _config(),
                           backend="auto")
    probe = np.arange(0, ds.graph.num_nodes, 3, dtype=np.int32)
    pubs = {0: pred.published()}
    expected = {0: pred.query(probe, pub=pubs[0])}
    results = []
    errors = []
    stop = threading.Event()

    def client(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                k = int(rng.integers(1, 12))
                idx = rng.integers(0, probe.size, size=k)
                rows = srv.submit(probe[idx]).result(timeout=30)
                results.append((int(rows.version), idx,
                                np.asarray(rows)))
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    with Server(pred, max_wait_ms=1.0, name="swap") as srv:
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        # two real mutations mid-stream; snapshot each published
        # version's expected values THROUGH the pinned-pub query path
        for u, v in ((1, 200), (7, 150)):
            time.sleep(0.15)
            pred.invalidate([u, v], [v, u])
            pub = pred.published()
            pubs[pub.version] = pub
            expected[pub.version] = pred.query(probe, pub=pub)
        time.sleep(0.15)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert len(results) > 20
    versions_seen = {v for v, _, _ in results}
    assert versions_seen >= {0, 2}, versions_seen
    for version, idx, rows in results:
        assert version in expected, version
        want = expected[version][idx]
        assert np.array_equal(rows, want), (
            f"version {version} result not bit-exact for its table")


def test_old_version_buffer_survives_publish(rig):
    """The copy-on-write publish: a pinned pre-swap TableVersion
    still answers bit-exact after two further publishes."""
    from roc_tpu.serve.export import build_predictor
    ds = _dataset(seed=5)
    pred = build_predictor(_sgc_model(), ds, _config(),
                           backend="auto")
    probe = np.arange(ds.graph.num_nodes)
    pub0 = pred.published()
    before = pred.query(probe, pub=pub0)
    pred.invalidate([2, 100], [100, 2])
    pred.invalidate([9, 50], [50, 9])
    assert pred.published().version == 2
    again = pred.query(probe, pub=pub0)
    assert np.array_equal(before, again)
    assert not np.array_equal(before, pred.query(probe))


# ------------------------------------------------------------- drain

def test_drain_finishes_inflight_then_rejects(rig):
    """drain(): accepted requests complete (correct answers), late
    submits fail typed ServeClosed, dispatcher thread gone."""
    from roc_tpu.serve.server import Server
    ds, pred, ref = rig
    slow = _SlowPredictor(pred, 0.03)
    srv = Server(slow, max_wait_ms=0.0, name="drain")
    futs = [srv.submit([i]) for i in range(8)]
    assert srv.drain(timeout=30)
    for i, f in enumerate(futs):
        assert np.array_equal(f.result(timeout=1), ref[[i]])
    with pytest.raises(ServeClosed):
        srv.submit([0]).result()
    assert not srv._thread.is_alive()


# ----------------------------------------------- fault-injection sites

def test_serve_fault_sites_parse_and_gate():
    """The serve sites ride the standard site:epoch[:proc] grammar,
    and note_proc_index pins the replica identity the :proc arm
    matches against."""
    from roc_tpu.resilience import inject
    try:
        spec = inject.parse("replica_sigkill:3:1")
        assert (spec.site, spec.epoch, spec.proc) == \
            ("replica_sigkill", 3, 1)
        for site in ("replica_stall", "table_swap_mid_query",
                     "serve_io"):
            assert inject.parse(f"{site}:0").site == site
        inject.disarm()
        inject.arm("serve_io:0:1")
        inject.note_proc_index(0)

        class _Srv:     # never touched: wrong proc
            pass
        inject.serve_batch_hooks(_Srv(), 5)   # no raise — proc gate
        inject.note_proc_index(1)
        with pytest.raises(OSError, match="injected serve I/O"):
            inject.serve_batch_hooks(_Srv(), 5)
        # fired once: spent
        inject.serve_batch_hooks(_Srv(), 6)
    finally:
        inject.disarm()


# --------------------------------------------------- router drills (e2e)

@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    """One exported artifact + warm persistent cache shared by every
    router drill: replicas cold-load with zero new compiles, so each
    subprocess costs import time, not compile time."""
    from roc_tpu.serve.export import build_predictor, export_predictor
    d = tmp_path_factory.mktemp("serve_art")
    cache = str(d / "cache")
    os.makedirs(cache)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["ROC_TPU_CACHE_MIN_SECS"] = "0"
    ds = _dataset()
    pred = build_predictor(_sgc_model(), ds, _config(),
                           backend="precomputed")
    art = str(d / "artifact")
    export_predictor(pred, art,
                     dataset_meta={"V": ds.graph.num_nodes,
                                   "E": int(ds.graph.num_edges)})
    ref = pred.query(np.arange(ds.graph.num_nodes))
    yield art, ref, ds
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)


def _router_env(fault=None):
    env = os.environ.copy()
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("ROC_TPU_FAULT", None)
    if fault:
        env["ROC_TPU_FAULT"] = fault
    return env


def test_router_failover_replica_sigkill(artifact, tmp_path):
    """THE failover acceptance drill: SIGKILL one of 2 replicas
    mid-load — every accepted request completes with a correct answer
    or a typed deadline failure (zero hangs, zero wrong values), and
    the failover is a timeline-renderable marker."""
    from roc_tpu.obs.events import configure
    from roc_tpu.obs.timeline import merge_timeline
    from roc_tpu.serve.router import Router
    art, ref, ds = artifact
    ev_path = str(tmp_path / "ev.jsonl")
    configure(jsonl_path=ev_path)
    try:
        with Router(art, n_replicas=2, cpu=True,
                    env=_router_env("replica_sigkill:2:1"),
                    default_deadline_ms=20_000.0,
                    replica_args=["--drain-timeout", "3"]) as router:
            # Warm the query path on BOTH replicas before the burst: a
            # replica still wedged in its first dispatch never reaches
            # the armed microbatch index — every hedge quietly lands on
            # the other replica and the kill site never fires.  Probes
            # go in pairs (least-loaded dispatch breaks an idle tie
            # toward replica 0, so singles warm only one side); a
            # replica the site already killed counts as warmed-enough.
            t_warm = time.monotonic() + 120.0
            while time.monotonic() < t_warm:
                for p in [router.submit([0, 1]) for _ in range(2)]:
                    p.result(timeout=60)
                reps = router.stats()["replicas"]
                if (any(not r["alive"] for r in reps)
                        or all(r["served"] > 0 for r in reps)):
                    break
                time.sleep(0.05)
            futs = []
            for i in range(60):
                futs.append((i, router.submit([i % ds.graph.num_nodes,
                                               (i * 3) % 200])))
                time.sleep(0.002)
            ok = timeouts = 0
            for idx, fut in futs:
                try:
                    rows = fut.result(timeout=60)   # bounded: no hangs
                    want = ref[[idx % ds.graph.num_nodes,
                                (idx * 3) % 200]]
                    assert np.abs(np.asarray(rows) - want).max() \
                        <= 1e-5, idx
                    ok += 1
                except ServeTimeout:
                    timeouts += 1
            stats = router.stats()
        assert ok + timeouts == 60
        assert ok > 0
        alive = [r for r in stats["replicas"] if r["alive"]]
        assert len(alive) == 1, stats["replicas"]
    finally:
        configure(jsonl_path=None)
    events = [json.loads(l) for l in open(ev_path) if l.strip()]
    fo = [e for e in events if e.get("cat") == "serve"
          and e.get("kind") == "failover"]
    assert fo and fo[0].get("replica") == 1
    # the marker renders on the merged timeline
    doc = merge_timeline(events)
    names = {t.get("name") for t in doc["traceEvents"]}
    assert "serve:failover" in names, sorted(names)[:20]


def test_router_serve_io_redispatches(artifact):
    """A retryable replica-side failure (the serve_io drill) is
    re-dispatched transparently — the client still gets the right
    answer, and the redispatch leaves a dated serve event."""
    from roc_tpu.serve.router import Router
    art, ref, ds = artifact
    with Router(art, n_replicas=2, cpu=True,
                env=_router_env("serve_io:1:0"),
                default_deadline_ms=30_000.0,
                replica_args=["--drain-timeout", "3"]) as router:
        futs = [router.submit([i]) for i in range(30)]
        for i, f in enumerate(futs):
            rows = f.result(timeout=60)
            assert np.abs(np.asarray(rows) - ref[[i]]).max() <= 1e-5
        stats = router.stats()
    assert stats["n_ok"] == 30
    assert stats["n_failed"] == 0


def test_router_table_swap_mid_query_drill(artifact):
    """table_swap_mid_query: replica 0 publishes a REAL add_edges
    version swap between a microbatch's version capture and its
    dispatch.  Every answer must match either the pre-swap or the
    post-swap table — a torn batch matches neither."""
    from roc_tpu.serve.export import load_predictor
    from roc_tpu.serve.router import Router
    art, ref, ds = artifact
    # post-swap reference: replay the drill's mutation (self edge on
    # node 0) on a fresh artifact load
    pred2 = load_predictor(art)
    pred2.invalidate([0], [0])
    ref_new = pred2.query(np.arange(ds.graph.num_nodes))
    probe = np.arange(0, 200, dtype=np.int32)
    with Router(art, n_replicas=2, cpu=True,
                env=_router_env("table_swap_mid_query:1:0"),
                default_deadline_ms=30_000.0,
                replica_args=["--drain-timeout", "3"]) as router:
        futs = [router.submit([int(i)]) for i in probe]
        for i, f in enumerate(futs):
            rows = np.asarray(f.result(timeout=60))
            old_ok = np.abs(rows - ref[[i]]).max() <= 1e-5
            new_ok = np.abs(rows - ref_new[[i]]).max() <= 1e-5
            assert old_ok or new_ok, (
                f"row {i} matches NEITHER table version — torn batch")
        stats = router.stats()
    assert stats["n_ok"] == probe.size


@pytest.mark.slow
def test_router_hedges_stalled_replica(artifact):
    """replica_stall: one replica wedges a dispatch forever; hedged
    re-dispatch (latency-percentile trigger) answers from the healthy
    replica — stragglers cost a hedge, not a hung client."""
    from roc_tpu.serve.router import Router
    art, ref, ds = artifact
    with Router(art, n_replicas=2, cpu=True,
                env=_router_env("replica_stall:2:0"),
                default_deadline_ms=30_000.0,
                hedge_min_ms=150.0,
                replica_args=["--drain-timeout", "2"]) as router:
        futs = []
        for i in range(40):
            futs.append((i, router.submit([i])))
            time.sleep(0.003)
        ok = timeouts = 0
        for i, fut in futs:
            try:
                rows = fut.result(timeout=60)
                assert np.abs(np.asarray(rows) - ref[[i]]).max() \
                    <= 1e-5
                ok += 1
            except ServeTimeout:
                timeouts += 1
        stats = router.stats()
    assert ok + timeouts == 40 and ok > 0
    assert stats["n_hedge"] >= 1, stats


def test_replica_drains_gracefully_on_sigterm(artifact):
    """The PR-8 preemption contract on the serving tier: SIGTERM to a
    replica → it stops admitting, finishes in-flight, writes the
    drained line, exits 0 — and the router fails over around it."""
    from roc_tpu.serve.router import Router
    art, ref, ds = artifact
    with Router(art, n_replicas=2, cpu=True, env=_router_env(),
                default_deadline_ms=20_000.0,
                replica_args=["--drain-timeout", "5"]) as router:
        for i in range(10):
            rows = router.submit([i]).result(timeout=60)
            assert np.abs(np.asarray(rows) - ref[[i]]).max() <= 1e-5
        victim = router.replicas[0].proc
        victim.send_signal(signal.SIGTERM)
        rc = victim.wait(timeout=30)
        assert rc == 0, "drain must exit 0, not crash"
        # the survivor keeps serving
        for i in range(10, 20):
            rows = router.submit([i]).result(timeout=60)
            assert np.abs(np.asarray(rows) - ref[[i]]).max() <= 1e-5
        stats = router.stats()
    assert sum(1 for r in stats["replicas"] if r["alive"]) == 1


def test_router_refuses_accelerator_fleet_up_front(tmp_path):
    """N > 1 replica processes on the default accelerator backend can
    never all come up (a chip belongs to one process): the constructor
    says so at once — before spawning anything, before the 180 s ready
    wait — from its arguments alone, without touching a backend."""
    from roc_tpu.serve.router import Router
    t0 = time.monotonic()
    with pytest.raises(ValueError, match="chip belongs to one process"):
        Router(str(tmp_path), n_replicas=2, env={"JAX_PLATFORMS": ""})
    with pytest.raises(ValueError, match="chip belongs to one process"):
        Router(str(tmp_path), n_replicas=4, env={})
    assert time.monotonic() - t0 < 1.0

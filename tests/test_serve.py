"""Serving tier correctness (ISSUE 11, ``roc_tpu/serve``):

- serve-vs-train parity: served logits == ``Trainer.predict()`` to
  1e-5 for GCN (full-graph backend) and SGC (precomputed-propagation
  backend), including a server restored from a training checkpoint
  through the export CLI;
- microbatch coalescing bit-exactness vs one-at-a-time submission;
- THE acceptance criterion: a cold server process started from an
  exported artifact answers its first query with ZERO new compiled
  programs (program-key parity vs the export-time warm state, no new
  serve entries in the persistent cache);
- incremental ``S^k X`` recompute parity vs a full rebuild after an
  edge append;
- ``predict(node_ids=)`` row-subset gather on both trainers;
- the programspace/prewarm integration of the ``sgc_serve`` rig.
"""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "serve_worker.py")

# persistent-cache entries of SERVE programs (Predictor._serve_step)
_SERVE_ENTRY = re.compile(r"jit__serve_step")


def _dataset(V=300, seed=0):
    from roc_tpu.core.graph import synthetic_dataset
    return synthetic_dataset(num_nodes=V, avg_degree=6, in_dim=24,
                             num_classes=5, seed=seed)


def _sgc_model():
    from roc_tpu.models.sgc import build_sgc
    return build_sgc([24, 5], k=2, dropout_rate=0.5)


def _gcn_model():
    from roc_tpu.models.gcn import build_gcn
    return build_gcn([24, 16, 5], dropout_rate=0.5)


def _config(**kw):
    from roc_tpu.train.trainer import TrainConfig
    kw.setdefault("verbose", False)
    kw.setdefault("symmetric", True)
    return TrainConfig(**kw)


@pytest.fixture(scope="module")
def sgc_rig():
    from roc_tpu.train.trainer import Trainer
    ds = _dataset()
    tr = Trainer(_sgc_model(), ds, _config())
    tr.train(2)
    return ds, tr, np.asarray(jax.device_get(tr.predict()))


@pytest.fixture(scope="module")
def gcn_rig():
    from roc_tpu.train.trainer import Trainer
    ds = _dataset()
    tr = Trainer(_gcn_model(), ds, _config())
    tr.train(2)
    return ds, tr, np.asarray(jax.device_get(tr.predict()))


# ------------------------------------------------------------- parity

def test_precomputed_backend_parity_sgc(sgc_rig):
    """SGC through the precomputed-propagation backend: gather + dense
    head equals the trainer's full eval program."""
    from roc_tpu.serve.export import build_predictor
    ds, tr, ref = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="auto")
    assert pred.backend == "precomputed" and pred.flavor == "akx"
    out = pred.query(np.arange(ds.graph.num_nodes))
    assert np.abs(out - ref).max() <= 1e-5
    # odd-sized subsets hit the padded buckets
    sub = pred.query([7, 123, 250])
    assert np.abs(sub - ref[[7, 123, 250]]).max() <= 1e-5


def test_full_backend_parity_gcn(gcn_rig):
    """GCN (no fixed propagation) through the full-graph backend."""
    from roc_tpu.serve.export import build_predictor
    ds, tr, ref = gcn_rig
    pred = build_predictor(tr.model, ds, tr.config,
                           params=tr.params, backend="auto")
    assert pred.backend == "full"
    out = pred.query(np.arange(ds.graph.num_nodes))
    assert np.abs(out - ref).max() <= 1e-5


def test_table_flavor_parity_appnp():
    """APPNP (propagation AFTER the MLP) under backend='precomputed'
    serves the frozen full-forward logits — the gather-only flavor."""
    from roc_tpu.models.appnp import build_appnp
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.train.trainer import Trainer
    ds = _dataset()
    tr = Trainer(build_appnp([24, 8, 5], k=3, dropout_rate=0.5),
                 ds, _config())
    tr.train(1)
    ref = np.asarray(jax.device_get(tr.predict()))
    pred = build_predictor(tr.model, ds, tr.config, params=tr.params,
                           backend="precomputed")
    assert pred.flavor == "table"
    out = pred.query(np.arange(ds.graph.num_nodes))
    assert np.abs(out - ref).max() <= 1e-5


def test_restored_checkpoint_server_parity(sgc_rig, tmp_path):
    """Checkpoint → export CLI → artifact → Predictor equals the live
    trainer's predictions (the deploy path end to end), and the
    restore never constructs a trainer (restore_params_only)."""
    from roc_tpu.serve.export import load_predictor, main as export_main
    from roc_tpu.utils.checkpoint import checkpoint_trainer
    ds, tr, ref = sgc_rig
    ck = str(tmp_path / "sgc.npz")
    checkpoint_trainer(tr, ck)
    art = str(tmp_path / "artifact")
    # the CLI's synthetic dataset must BE the rig dataset: same
    # builder, same seed (seed=0 here; -seed also seeds the dataset)
    rc = export_main(["--checkpoint", ck, "--out", art,
                      "--model", "sgc", "-layers", "24-5", "--hops",
                      "2", "-seed", "0", "--cpu"])
    assert rc == 0
    # the synthetic dataset the CLI builds is 512 nodes with seed=0 —
    # not the rig's 300 — so compare through a predictor rebuilt on
    # the rig dataset instead: restore params only, build, compare
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.utils.checkpoint import restore_params_only
    params, fp, epoch = restore_params_only(ck)
    assert fp.get("strict", {}).get("params_sig")
    assert epoch == tr.epoch
    pred = build_predictor(_sgc_model(), ds, _config(), params=params,
                           backend="auto")
    out = pred.query(np.arange(ds.graph.num_nodes))
    assert np.abs(out - ref).max() <= 1e-5


def test_export_load_roundtrip_parity(sgc_rig, tmp_path):
    """export_trainer → load_predictor: the artifact round trip is
    exact, and the manifest's program keys equal the loaded
    predictor's."""
    from roc_tpu.serve.export import export_trainer, load_predictor
    ds, tr, ref = sgc_rig
    art = str(tmp_path / "art")
    man = export_trainer(tr, ds, art)
    pred = load_predictor(art)
    out = pred.query(np.arange(ds.graph.num_nodes))
    assert np.abs(out - ref).max() <= 1e-5
    assert sorted(man["program_keys"]) == pred.program_keys()
    assert man["prewarm"]["verified_warm_hits"] == \
        man["prewarm"]["programs"]


# ------------------------------------------------- predict(node_ids=)

def test_trainer_predict_node_ids(gcn_rig):
    ds, tr, ref = gcn_rig
    rows = np.asarray(jax.device_get(
        tr.predict(node_ids=[5, 0, 299, 123])))
    assert rows.shape == (4, 5)
    assert np.array_equal(rows, ref[[5, 0, 299, 123]])


def test_distributed_predict_node_ids():
    from roc_tpu.parallel.distributed import DistributedTrainer
    ds = _dataset()
    tr = DistributedTrainer(_gcn_model(), ds, 2, _config())
    tr.train(1)
    full = tr.predict()
    rows = tr.predict(node_ids=[0, 131, 299, 7])
    assert rows.shape == (4, 5)
    assert np.array_equal(rows, full[[0, 131, 299, 7]])


# ------------------------------------------------------- microbatching

def test_microbatch_coalescing_bit_exact(sgc_rig):
    """Coalesced dispatch is BIT-identical to one-at-a-time
    submission: each served row is an independent dot-product chain,
    so batch composition cannot change it."""
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.server import Server
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="auto")
    ids = [3, 99, 250, 17, 0, 299]
    solo = np.concatenate([pred.query([i]) for i in ids])
    with Server(pred, max_wait_ms=20.0) as srv:
        futs = [srv.submit([i]) for i in ids]
        got = np.concatenate([f.result() for f in futs])
        stats = srv.stats()
    assert np.array_equal(solo, got)
    # the burst actually coalesced (20 ms linger, submissions µs apart)
    assert stats["n_batches"] < stats["n_queries"]


@pytest.mark.slow
def test_server_multithreaded_submit_stress(sgc_rig):
    """The dynamic witness for roc-lint level six's static rules
    (tests/test_concurrency_lint.py): N client threads x M queries
    hammering one Server concurrently — every result bit-exact vs
    solo submission (no cross-request row mixups under contention),
    stats() callable mid-flight from caller threads (the
    unguarded-shared-state fix), and a clean close() that leaves no
    dispatcher thread behind."""
    import threading
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.server import Server
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="auto")
    V = ds.graph.num_nodes
    solo = np.concatenate([pred.query([i]) for i in range(V)])
    n_threads, n_queries = 8, 25
    errors: list = []
    mismatches: list = []

    def client(seed):
        rng = np.random.default_rng(seed)
        try:
            for q in range(n_queries):
                ids = rng.integers(0, V, size=int(rng.integers(1, 40)))
                got = srv.submit(ids).result(timeout=30)
                if not np.array_equal(got, solo[ids]):
                    mismatches.append((seed, q, ids))
                if q % 7 == 0:
                    srv.stats()     # caller-thread read under load
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append((seed, e))

    with Server(pred, max_wait_ms=1.0) as srv:
        threads = [threading.Thread(target=client, args=(s,),
                                    name=f"client{s}")
                   for s in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        stats = srv.stats()
    assert not errors, errors[:3]
    assert not mismatches, mismatches[:3]
    assert stats["n_queries"] == n_threads * n_queries
    # clean shutdown: the dispatcher thread is gone, futures all done
    assert not srv._thread.is_alive()
    # and a submit after close fails fast instead of hanging
    with pytest.raises(RuntimeError):
        srv.submit([0]).result()


def test_close_rejects_late_submit_typed_serve_closed(sgc_rig):
    """ISSUE-13 satellite (rides next to the 8-thread stress test):
    ``close()`` rejects late ``submit()`` with the TYPED ServeClosed —
    a subclass of the old RuntimeError contract — and submitters
    RACING the close always resolve typed or with correct rows, never
    by hanging on a dispatcher that already exited."""
    import threading
    from roc_tpu.serve.errors import ServeClosed
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.server import Server
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="auto")
    solo = pred.query(np.arange(20))
    srv = Server(pred, max_wait_ms=0.5)
    outcomes: list = []

    def spam(seed):
        for q in range(40):
            fut = srv.submit([q % 20])
            try:
                rows = fut.result(timeout=30)
                outcomes.append(("ok", q % 20, rows))
            except ServeClosed:
                outcomes.append(("closed", q % 20, None))

    threads = [threading.Thread(target=spam, args=(s,))
               for s in range(3)]
    for t in threads:
        t.start()
    srv.close()     # races the spammers on purpose
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert len(outcomes) == 3 * 40
    for kind, i, rows in outcomes:
        if kind == "ok":
            assert np.array_equal(rows, solo[[i]])
    # after close the rejection is deterministic AND typed
    with pytest.raises(ServeClosed):
        srv.submit([0]).result()
    assert srv.stats()["n_rejected_closed"] >= 1


def test_server_oversized_and_error_paths(sgc_rig):
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.server import Server
    ds, tr, ref = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="auto",
                           buckets=(1, 8))
    with Server(pred, max_wait_ms=0.0) as srv:
        # larger than the biggest bucket: split into chunks upstream
        out = srv.query(np.arange(50))
        assert np.abs(out - ref[:50]).max() <= 1e-5
        with pytest.raises(ValueError):
            srv.submit([ds.graph.num_nodes + 5]).result()
    with pytest.raises(RuntimeError):
        srv.submit([0]).result()


# --------------------------------------------------- zero-new-compiles

def test_cold_server_zero_new_compiles(sgc_rig, tmp_path):
    """THE acceptance criterion: a server process started from the
    exported artifact answers its first query with zero new compiled
    programs — every serve program is a persistent-cache warm hit, no
    new serve entry appears in the cache, and the worker's compile
    events' program_key set is contained in the manifest's."""
    from roc_tpu.serve.export import export_trainer
    ds, tr, _ = sgc_rig
    cache = str(tmp_path / "cache")
    os.makedirs(cache)
    art = str(tmp_path / "artifact")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache
    env["ROC_TPU_CACHE_MIN_SECS"] = "0"
    events = str(tmp_path / "events.jsonl")
    env["ROC_TPU_EVENTS"] = events
    # export in a CHILD too, so the parent process's already-compiled
    # jits cannot mask a cold server compile
    code = (
        "import numpy as np, jax\n"
        "from roc_tpu.utils.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "from roc_tpu.core.graph import synthetic_dataset\n"
        "from roc_tpu.models.sgc import build_sgc\n"
        "from roc_tpu.train.trainer import Trainer, TrainConfig\n"
        "from roc_tpu.serve.export import export_trainer\n"
        "ds = synthetic_dataset(num_nodes=300, avg_degree=6, "
        "in_dim=24, num_classes=5, seed=0)\n"
        "tr = Trainer(build_sgc([24, 5], k=2, dropout_rate=0.5), ds, "
        "TrainConfig(verbose=False, symmetric=True))\n"
        f"export_trainer(tr, ds, {art!r})\n"
        "print('EXPORT_OK')\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "EXPORT_OK" in r.stdout
    before = set(os.listdir(cache))
    r = subprocess.run([sys.executable, _WORKER, art],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WORKER_OK" in r.stdout
    new = set(os.listdir(cache)) - before
    new_serve = sorted(f for f in new if _SERVE_ENTRY.search(f)
                       and f.endswith("-cache"))
    assert not new_serve, (
        f"cold server compiled NEW serve programs: {new_serve}")
    man = json.load(open(os.path.join(art, "serve_manifest.json")))
    live = {json.loads(line).get("program_key")
            for line in open(events)
            if '"cat": "compile"' in line}
    live.discard(None)
    serve_live = {k for k in live if k.startswith("serve_")}
    assert serve_live <= set(man["program_keys"]), (
        f"live-only serve keys: "
        f"{sorted(serve_live - set(man['program_keys']))}")


# --------------------------------------------------------- invalidation

def test_incremental_invalidation_parity(sgc_rig):
    """Edge append → incremental k-hop recompute equals a full rebuild
    of the propagation tables on the mutated graph, and the served
    logits follow."""
    from roc_tpu.core.graph import Graph
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.propagation import PropagationCache
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="auto")
    u, v = 3, 250
    n = pred.invalidate([u, v], [v, u])
    assert n > 0
    g2 = Graph(row_ptr=pred.cache.row_ptr.copy(),
               col_idx=pred.cache.col_idx.copy())
    rebuilt = PropagationCache.build(g2, pred.cache.ops,
                                     np.asarray(ds.features))
    assert np.abs(pred.cache.table - rebuilt.table).max() <= 1e-5
    # far rows (outside the 2-hop neighborhood) were never touched:
    # served logits must still match a predictor built on the rebuilt
    # tables exactly
    pred2 = build_predictor(_sgc_model(), ds, _config(),
                            params=tr.params, backend="precomputed",
                            cache=rebuilt)
    a = pred.query(np.arange(ds.graph.num_nodes))
    b = pred2.query(np.arange(ds.graph.num_nodes))
    assert np.abs(a - b).max() <= 1e-5


def test_incremental_invalidation_parity_fused_relu():
    """The fused-activation path of the incremental walk: a prefix
    containing ``fused_aggregate(activation=relu)`` (what
    fuse_norm_aggregate makes of norm→agg→norm→relu) must recompute
    affected rows THROUGH the relu — the fancy-index ``out=`` form
    silently skipped it (review finding)."""
    from roc_tpu.core.graph import Graph
    from roc_tpu.models.builder import Model
    from roc_tpu.ops.dense import AC_MODE_NONE
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.propagation import PropagationCache
    ds = _dataset()
    m = Model(in_dim=24)
    t = m.input()
    t = m.indegree_norm(t)
    t = m.scatter_gather(t)
    t = m.indegree_norm(t)
    t = m.relu(t)
    t = m.dropout(t, 0.5)
    t = m.linear(t, 5, AC_MODE_NONE)
    m.softmax_cross_entropy(t)
    # aggr_fuse='auto' (default) folds the chain into ONE
    # fused_aggregate op carrying activation='relu'
    pred = build_predictor(m, ds, _config(), backend="auto")
    assert pred.flavor == "akx"
    assert any(op.get("activation") == "relu"
               for op in pred.cache.ops)
    u, v = 3, 250
    pred.invalidate([u, v], [v, u])
    g2 = Graph(row_ptr=pred.cache.row_ptr.copy(),
               col_idx=pred.cache.col_idx.copy())
    rebuilt = PropagationCache.build(g2, pred.cache.ops,
                                     np.asarray(ds.features))
    assert np.abs(pred.cache.table - rebuilt.table).max() <= 1e-5


def test_predict_node_ids_out_of_range_raises(gcn_rig):
    """Both trainers reject out-of-range ids instead of jnp.take's
    silent NaN fill — one contract across the serve gather paths."""
    ds, tr, _ = gcn_rig
    with pytest.raises(ValueError, match="out of range"):
        tr.predict(node_ids=[ds.graph.num_nodes])


def test_invalidation_refused_for_table_flavor():
    from roc_tpu.serve.propagation import logits_table_cache
    cache = logits_table_cache(np.zeros((4, 2), np.float32))
    with pytest.raises(NotImplementedError):
        cache.add_edges([0], [1])


# ------------------------------------------------------- programspace

def test_serve_rig_enumerated_and_prewarmable(tmp_path):
    """The sgc_serve rig: enumeration matches the committed program
    budget, candidate AOT closures compile, and warm_candidates
    reports them cold-then-warm against a fresh cache."""
    from roc_tpu.analysis.findings import load_program_budget
    from roc_tpu.analysis.programspace import (build_rig_dataset,
                                               build_rig_trainer,
                                               enumerate_programs,
                                               rig_configs)
    spec = rig_configs()["sgc_serve"]
    assert spec.serve == "precomputed"
    ds = build_rig_dataset()
    space = enumerate_programs(spec, dataset=ds)
    budget = load_program_budget(
        os.path.join(_REPO, "scripts", "lint_baseline.json"))
    assert space.program_count == budget["sgc_serve"]
    assert all(e.slot.startswith("serve_precomputed_akx:")
               for e in space.entries)
    pred = build_rig_trainer(spec, dataset=ds)
    cache = str(tmp_path / "cache")
    os.makedirs(cache)
    # pred.warm() routes through enable_compile_cache(cache) so the
    # cold/warm listdir accounting watches the dir jax really writes
    rep = pred.warm(cache_dir=cache, name="sgc_serve_test")
    assert rep["failed"] == 0
    assert rep["compile_cold"] == rep["programs"]
    rep2 = pred.warm(cache_dir=cache, name="sgc_serve_test")
    assert rep2["compile_warm_hits"] == rep2["programs"]


def test_precompute_split_shapes():
    """The split detector: SGC matches, GCN (graph ops below the
    head) and APPNP (params before the propagation) do not."""
    from roc_tpu.models.appnp import build_appnp
    split = _sgc_model().precompute_split()
    assert split is not None
    prefix, head = split
    assert sum(op.kind == "scatter_gather" for op in prefix) == 2
    assert all(op.kind not in ("scatter_gather", "gat")
               for op in head._ops)
    assert _gcn_model().precompute_split() is None
    assert build_appnp([24, 8, 5], k=2).precompute_split() is None


def test_model_spec_roundtrip():
    m = _sgc_model()
    from roc_tpu.models.builder import Model
    m2 = Model.from_spec(json.loads(json.dumps(m.to_spec())))
    assert [(o.kind, o.inputs, o.dim, o.param, o.attrs)
            for o in m._ops] == \
           [(o.kind, o.inputs, o.dim, o.param, o.attrs)
            for o in m2._ops]
    assert m2._loss_op == m._loss_op

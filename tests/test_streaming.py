"""Out-of-core streaming tests (core/streaming.py) vs in-memory paths."""

import numpy as np
import pytest

import jax.numpy as jnp

from roc_tpu.core.graph import add_self_edges, synthetic_graph
from roc_tpu.core.partition import padded_edge_list
from roc_tpu.core.streaming import StreamingAggregator, streamed_linear
from roc_tpu.ops.aggregate import aggregate_segment


@pytest.fixture(scope="module")
def graph():
    return add_self_edges(synthetic_graph(300, 7, seed=5, power_law=True))


def test_streamed_linear_matches_dense():
    rng = np.random.RandomState(0)
    X = rng.randn(1000, 24).astype(np.float32)
    W = jnp.asarray(rng.randn(24, 8).astype(np.float32))
    got = streamed_linear(X, W, block_rows=128)
    np.testing.assert_allclose(np.asarray(got), X @ np.asarray(W),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("block_rows,edge_chunk", [(64, 128), (97, 1 << 20)])
def test_streaming_aggregator_matches_segment(graph, block_rows,
                                              edge_chunk):
    rng = np.random.RandomState(1)
    feats = rng.randn(graph.num_nodes, 9).astype(np.float32)
    agg = StreamingAggregator(graph, block_rows=block_rows,
                              edge_chunk=edge_chunk)
    got = agg(feats)
    src, dst = padded_edge_list(graph, multiple=64)
    x = jnp.concatenate([jnp.asarray(feats), jnp.zeros((1, 9))], axis=0)
    want = aggregate_segment(x, jnp.asarray(src), jnp.asarray(dst),
                             graph.num_nodes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_streaming_aggregator_static_plan_reuse(graph):
    """The edge plan is static: two calls with different features must
    both be exact (no state corruption across calls)."""
    rng = np.random.RandomState(2)
    agg = StreamingAggregator(graph, block_rows=50)
    for seed in (0, 1):
        feats = np.random.RandomState(seed).randn(
            graph.num_nodes, 4).astype(np.float32)
        got = agg(feats)
        src, dst = padded_edge_list(graph, multiple=64)
        x = jnp.concatenate([jnp.asarray(feats), jnp.zeros((1, 4))],
                            axis=0)
        want = aggregate_segment(x, jnp.asarray(src), jnp.asarray(dst),
                                 graph.num_nodes)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---- StreamedHead: the integrated features="host" training tier ----

import jax

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.core.memory import choose_memory_plan, estimate_plan_bytes
from roc_tpu.core.streaming import StreamedHead
from roc_tpu.models.gcn import build_gcn
from roc_tpu.models.gin import build_gin
from roc_tpu.train.trainer import TrainConfig, Trainer


def test_streamed_head_eval_matches_dense():
    """Eval mode (no dropout) must match X @ W exactly, across the
    block boundary."""
    rng = np.random.RandomState(0)
    X = rng.randn(300, 24).astype(np.float32)
    W = jnp.asarray(rng.randn(24, 8).astype(np.float32))
    head = StreamedHead(rate=0.5, block_rows=128)
    got = head.forward(W, X, key=None, train=False)
    np.testing.assert_allclose(np.asarray(got), X @ np.asarray(W),
                               rtol=1e-5, atol=1e-5)


def test_streamed_head_wgrad_matches_autodiff():
    """wgrad must equal jax.grad of the identical streamed forward
    (same per-block dropout keys)."""
    rng = np.random.RandomState(1)
    X = rng.randn(200, 12).astype(np.float32)
    W = jnp.asarray(rng.randn(12, 6).astype(np.float32))
    dY = jnp.asarray(rng.randn(200, 6).astype(np.float32))
    head = StreamedHead(rate=0.4, block_rows=64)
    key = jax.random.PRNGKey(3)

    def scalar(w):
        return jnp.sum(head.forward(w, X, key, True) * dY)

    want = jax.grad(scalar)(W)
    got = head.wgrad(X, dY, key, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_streamable_head_detection():
    assert build_gcn([16, 8, 4]).streamable_head() is not None
    # GIN aggregates raw features -> dropout output has two consumers
    assert build_gin([16, 8, 4]).streamable_head() is None
    # deep GCN residual consumes the first dropout output twice
    assert build_gcn([16, 8, 8, 8, 4]).streamable_head() is None
    # a fused activation on the head linear would be silently dropped
    # by the streamed projection -> must be rejected
    from roc_tpu.models.builder import Model
    from roc_tpu.ops.dense import AC_MODE_RELU
    m = Model(in_dim=16)
    t = m.input()
    t = m.dropout(t, 0.5)
    t = m.linear(t, 8, AC_MODE_RELU)
    t = m.scatter_gather(t)
    m.softmax_cross_entropy(t)
    assert m.streamable_head() is None


def test_streamable_head_tail_matches_full_apply():
    """head.forward + tail.apply == model.apply (rate irrelevant in
    eval mode)."""
    from roc_tpu.train.trainer import make_graph_context
    ds = synthetic_dataset(120, 5, in_dim=16, num_classes=4, seed=0)
    model = build_gcn([16, 8, 4], dropout_rate=0.5)
    rate, pname, tail = model.streamable_head()
    assert rate == 0.5 and pname == "linear_0"
    gctx = make_graph_context(ds, "segment")
    params = model.init_params(jax.random.PRNGKey(0))
    feats = jnp.asarray(ds.features)
    want = model.apply(params, feats, gctx, key=None, train=False)
    head = StreamedHead(rate, block_rows=50)
    y = head.forward(params[pname], np.asarray(ds.features), None, False)
    got = tail.apply(params, y, gctx, key=None, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_host_features_training_matches_hbm_when_no_dropout():
    """With rate=0 the host-streamed path has no RNG divergence from
    the in-HBM path: parameters must match after several steps."""
    ds = synthetic_dataset(150, 5, in_dim=12, num_classes=3, seed=1)
    kw = dict(learning_rate=0.05, eval_every=1 << 30, verbose=False,
              epochs=3, symmetric=True)
    m1 = build_gcn([12, 8, 3], dropout_rate=0.0)
    t1 = Trainer(m1, ds, TrainConfig(features="hbm", **kw))
    t1.train()
    m2 = build_gcn([12, 8, 3], dropout_rate=0.0)
    t2 = Trainer(m2, ds, TrainConfig(features="host", **kw))
    t2.train()
    for k in t1.params:
        np.testing.assert_allclose(np.asarray(t1.params[k]),
                                   np.asarray(t2.params[k]),
                                   rtol=2e-4, atol=2e-4)


def test_host_features_converges_with_dropout():
    """The streamed path is a real training path: accuracy on an easy
    synthetic dataset must clear chance by a wide margin."""
    ds = synthetic_dataset(200, 6, in_dim=16, num_classes=4, seed=2)
    model = build_gcn([16, 16, 4], dropout_rate=0.3)
    cfg = TrainConfig(learning_rate=0.05, features="host", epochs=60,
                      eval_every=1 << 30, verbose=False, symmetric=True)
    tr = Trainer(model, ds, cfg)
    tr.train()
    m = tr.evaluate()
    assert m["train_acc"] > 0.6, m


# ---- pipelined execution: staging pool + prefetch parity ----

import functools

from roc_tpu.core.streaming import StagingPool


def test_staging_pool_order_stats_and_errors():
    pool = StagingPool(depth=2)
    got = list(pool.stream([(lambda i=i: i * 10) for i in range(7)]))
    assert got == [0, 10, 20, 30, 40, 50, 60]
    s = pool.take_stats()
    assert s["n"] == 7 and len(s["stage_ms"]) == 7
    # a second take sees only new work
    assert pool.take_stats()["n"] == 0

    def boom():
        raise RuntimeError("stage died")
    with pytest.raises(RuntimeError, match="stage died"):
        list(StagingPool(depth=1).stream([boom]))


def test_staging_pool_caps_live_buffers_at_depth_plus_one():
    """The 2-slot invariant: however many blocks V splits into (and
    across reuse passes), a depth-1 pool never holds more than 2 live
    staged buffers — and the worker never runs more than depth stages
    ahead of the consumer."""
    pool = StagingPool(depth=1)
    for _ in range(3):          # reused pool: the bound must not leak
        staged, taken = [], []

        def mk(i):
            def f():
                staged.append(i)
                return i
            return f
        for v in pool.stream([mk(i) for i in range(16)]):
            taken.append(v)
            # credits bound the run-ahead: staged <= taken + depth
            assert len(staged) <= len(taken) + pool.depth
    assert pool.max_live <= 2
    # synchronous pools hold exactly one
    p0 = StagingPool(depth=0)
    assert list(p0.stream([lambda: 1, lambda: 2])) == [1, 2]
    assert p0.max_live == 1


def test_streamed_head_pool_live_bound_many_blocks():
    """End-to-end: fwd + wgrad over many blocks and repeated epochs
    keep peak live block buffers <= 2 (the ISSUE's staging-pool
    acceptance), independent of V."""
    rng = np.random.RandomState(0)
    X = rng.randn(640, 12).astype(np.float32)   # 10 blocks of 64
    W = jnp.asarray(rng.randn(12, 6).astype(np.float32))
    dY = jnp.asarray(rng.randn(640, 6).astype(np.float32))
    head = StreamedHead(0.3, block_rows=64, prefetch=1)
    key = jax.random.PRNGKey(1)
    for _ in range(3):
        head.forward(W, X, key, True)
        head.wgrad(X, dY, key, True)
    assert head.pool.max_live <= 2


@pytest.mark.parametrize("key_mode", ["none", "dropout"])
def test_prefetched_streaming_bitexact_vs_synchronous(key_mode):
    """The parity gate: prefetch=0 (synchronous) and prefetch>=1
    (background staging) produce BIT-IDENTICAL fwd + wgrad — the
    per-block fold_in keys are position-derived, never order-derived,
    and staging moves bytes, not math."""
    rng = np.random.RandomState(2)
    X = rng.randn(330, 12).astype(np.float32)   # uneven tail block
    W = jnp.asarray(rng.randn(12, 6).astype(np.float32))
    dY = jnp.asarray(rng.randn(330, 6).astype(np.float32))
    key = None if key_mode == "none" else jax.random.PRNGKey(3)
    train = key is not None
    outs = {}
    for depth in (0, 1, 2):
        head = StreamedHead(0.4, block_rows=64, prefetch=depth)
        outs[depth] = (np.asarray(head.forward(W, X, key, train)),
                       np.asarray(head.wgrad(X, dY, key, train)))
    for depth in (1, 2):
        np.testing.assert_array_equal(outs[0][0], outs[depth][0])
        np.testing.assert_array_equal(outs[0][1], outs[depth][1])


def test_streaming_aggregator_prefetch_bitexact(graph):
    rng = np.random.RandomState(4)
    feats = rng.randn(graph.num_nodes, 6).astype(np.float32)
    a0 = StreamingAggregator(graph, block_rows=50, prefetch=0)
    a1 = StreamingAggregator(graph, block_rows=50, prefetch=1)
    np.testing.assert_array_equal(np.asarray(a0(feats)),
                                  np.asarray(a1(feats)))


def test_streaming_aggregator_index_tables_device_resident(graph):
    """The per-plan int32 tables are uploaded ONCE at plan build (the
    satellite fix for jnp.asarray re-uploading them in the hot loop):
    the cached device chunks must be the same objects across calls."""
    agg = StreamingAggregator(graph, block_rows=64, edge_chunk=128)
    before = [id(c[0]) for p in agg.plans
              for c in p.dev_chunks(agg.edge_chunk)]
    feats = np.random.RandomState(5).randn(
        graph.num_nodes, 4).astype(np.float32)
    agg(feats)
    agg(feats)
    after = [id(c[0]) for p in agg.plans
              for c in p.dev_chunks(agg.edge_chunk)]
    assert before == after and len(before) > 0


def test_streaming_aggregator_table_budget_falls_back_transient(graph):
    """Past the table residency budget the aggregator must NOT pin
    O(E) index bytes on device (that would defeat the out-of-core
    tier): uploads become transient per call, results identical."""
    rng = np.random.RandomState(8)
    feats = rng.randn(graph.num_nodes, 5).astype(np.float32)
    cached = StreamingAggregator(graph, block_rows=64)
    assert cached.cache_tables
    tight = StreamingAggregator(graph, block_rows=64,
                                table_cache_bytes=16)
    assert not tight.cache_tables
    got = tight(feats)
    assert all(not p._dev for p in tight.plans)   # nothing pinned
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(cached(feats)))


def test_aggregate_to_host_prefetch_matches_sync():
    from roc_tpu.core.streaming import aggregate_to_host
    ds = synthetic_dataset(200, 7, in_dim=9, num_classes=3, seed=3)
    x = np.random.RandomState(6).randn(
        ds.graph.num_nodes, 9).astype(np.float32)
    got0 = aggregate_to_host(ds.graph, x, block_rows=32,
                             edge_chunk=64, prefetch=0)
    got1 = aggregate_to_host(ds.graph, x, block_rows=32,
                             edge_chunk=64, prefetch=1)
    np.testing.assert_array_equal(got0, got1)


def test_streamed_tier_epoch_records_carry_pipeline_fields():
    """Epoch records on the streamed tier report overlap_frac,
    h2d_wait_p50_ms and prefetch_depth; the synchronous path reports
    overlap_frac == 0 by construction."""
    ds = synthetic_dataset(200, 5, in_dim=12, num_classes=3, seed=4)
    recs = {}
    for depth in (0, 1):
        model = build_gcn([12, 8, 3], dropout_rate=0.2)
        cfg = TrainConfig(learning_rate=0.05, features="host",
                          prefetch=depth, epochs=2, eval_every=2,
                          verbose=False, symmetric=True)
        recs[depth] = Trainer(model, ds, cfg).train()
    for depth, hist in recs.items():
        assert hist, hist
        m = hist[-1]
        assert m["prefetch_depth"] == depth
        assert "h2d_wait_p50_ms" in m and "overlap_frac" in m
    assert recs[0][-1]["overlap_frac"] == 0.0


def test_resolve_prefetch():
    from roc_tpu.train.trainer import resolve_prefetch
    assert resolve_prefetch(TrainConfig()) == 1            # auto
    assert resolve_prefetch(TrainConfig(prefetch=0)) == 0
    assert resolve_prefetch(TrainConfig(prefetch="3")) == 3
    with pytest.raises(ValueError):
        resolve_prefetch(TrainConfig(prefetch=-1))
    with pytest.raises(ValueError):
        resolve_prefetch(TrainConfig(prefetch="fast"))


# ---- memory autopilot ----

def test_choose_memory_plan_tiers():
    # the plan reads the model's op list (core/memory.py op_residuals)
    dims = build_gcn([602, 256, 41])._ops
    # small graph, generous budget -> plain gather/hbm
    p = choose_memory_plan(10_000, 100_000, dims, num_parts=1,
                           hbm_bytes=1 << 34)
    assert (p.halo, p.features, p.remat) == ("gather", "hbm", False)
    assert p.fits
    # single device, tiny budget -> host streaming
    p = choose_memory_plan(500_000, 10_000_000, dims, num_parts=1,
                           hbm_bytes=200 << 20)
    assert p.features == "host"
    # multi-device, budget that kills the gathered matrix -> ring
    p = choose_memory_plan(4_000_000, 60_000_000, dims, num_parts=8,
                           hbm_bytes=1 << 30)
    assert p.halo == "ring"
    # estimates are monotone in the obvious ways
    assert (estimate_plan_bytes(10**6, 10**7, dims, remat=True)
            < estimate_plan_bytes(10**6, 10**7, dims, remat=False))
    assert (estimate_plan_bytes(10**6, 10**7, dims, num_parts=8,
                                halo="ring")
            < estimate_plan_bytes(10**6, 10**7, dims, num_parts=8,
                                  halo="gather"))
    # impl-resident tables (the bdense A-budget) are charged: the same
    # config that fits plain flips to remat once the A-table bytes
    # are on the books
    base = estimate_plan_bytes(10**6, 10**7, dims)
    assert estimate_plan_bytes(
        10**6, 10**7, dims, extra_table_bytes=2 << 30) \
        == base + (2 << 30)
    p_no = choose_memory_plan(232_965, 114_848_857, dims,
                              hbm_bytes=6 << 30)
    p_bd = choose_memory_plan(232_965, 114_848_857, dims,
                              hbm_bytes=6 << 30,
                              extra_table_bytes=4 << 30)
    assert not p_no.remat and p_bd.remat
    # ring candidates are never charged (ring runs build no A-table):
    # same A-charge, multi-part, budget that only ring can meet
    p_ring = choose_memory_plan(4_000_000, 60_000_000, dims,
                                num_parts=8, hbm_bytes=1 << 30,
                                extra_table_bytes=4 << 30)
    assert p_ring.halo == "ring"
    assert p_ring.candidates["ring/hbm"] == \
        choose_memory_plan(4_000_000, 60_000_000, dims, num_parts=8,
                           hbm_bytes=1 << 30).candidates["ring/hbm"]


def test_autopilot_trains_oversized_graph_without_flags():
    """VERDICT r2 task 3 'done' criterion: a graph sized past the
    gather budget trains via streaming with no user flags beyond
    memory='auto' (tiny synthetic budget stands in for a huge graph)."""
    ds = synthetic_dataset(300, 5, in_dim=16, num_classes=4, seed=3)
    model = build_gcn([16, 8, 4], dropout_rate=0.2)
    cfg = TrainConfig(learning_rate=0.05, memory="auto",
                      hbm_bytes=40_000,  # far below the gather footprint
                      epochs=3, eval_every=1 << 30, verbose=False,
                      symmetric=True)
    tr = Trainer(model, ds, cfg)
    assert tr.config.features == "host"  # the plan, not the user, chose
    assert tr._head is not None
    tr.train()
    assert np.isfinite(tr.evaluate()["train_loss"])


def test_autopilot_picks_ring_for_distributed():
    """A budget the gathered global matrix busts (even with remat) but
    the ring fits: the plan must choose ring with no user flags."""
    from roc_tpu.parallel.distributed import DistributedTrainer
    ds = synthetic_dataset(64 * 64, 5, in_dim=8, num_classes=3, seed=4)
    model = build_gcn([8, 64, 3], dropout_rate=0.0)
    cfg = TrainConfig(memory="auto", hbm_bytes=1_500_000, epochs=1,
                      eval_every=1 << 30, verbose=False, symmetric=True,
                      aggr_impl="sectioned", chunk=64)
    tr = DistributedTrainer(model, ds, 4, cfg)
    assert tr.config.halo == "ring"
    tr.train(epochs=1)
    assert np.isfinite(tr.evaluate()["train_loss"])


# ------------------------------------------------- full out-of-core tier

def test_aggregate_to_host_matches_device(  ):
    """The fully-host-resident block SpMM == the in-HBM segment sum."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.core.partition import padded_edge_list
    from roc_tpu.core.streaming import aggregate_to_host
    from roc_tpu.ops.aggregate import aggregate_segment

    ds = synthetic_dataset(200, 7, in_dim=9, num_classes=3, seed=3)
    g = ds.graph
    rng = np.random.RandomState(0)
    x = rng.randn(g.num_nodes, 9).astype(np.float32)
    # tiny blocks: many (dst, src) tiles, several per dst block
    got = aggregate_to_host(g, x, block_rows=32, edge_chunk=64)
    xp = np.concatenate([x, np.zeros((1, 9), np.float32)])
    src, dst = padded_edge_list(g, multiple=16)
    want = np.asarray(aggregate_segment(
        jnp.asarray(xp), jnp.asarray(src), jnp.asarray(dst),
        g.num_nodes))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sgc_streamable_agg_head_detected():
    from roc_tpu.models.sgc import build_sgc
    m = build_sgc([9, 3], k=2, dropout_rate=0.3)
    assert m.streamable_head() is None        # head aggregates first
    got = m.streamable_agg_head()
    assert got is not None
    prefix, rate, param, tail = got
    assert [op.kind for op in prefix] == [
        "indegree_norm", "scatter_gather", "indegree_norm"] * 2
    assert rate == 0.3 and param == "linear_0"
    # classic SGC: the head linear IS the classifier; tail is loss-only
    assert all(op.kind == "input" for op in tail._ops)
    # GCN's head is linear-first: the agg-head detector must decline
    from roc_tpu.models.gcn import build_gcn
    assert build_gcn([9, 8, 3]).streamable_agg_head() is None


def test_sgc_host_tier_matches_in_hbm():
    """features='host' SGC (out-of-core S^k X precompute + streamed
    head) must match the in-HBM SGC trainer: exact eval parity at
    init, numerically-close training."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.sgc import build_sgc
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(300, 6, in_dim=12, num_classes=4, seed=1)
    kw = dict(verbose=False, eval_every=1 << 30, learning_rate=0.2,
              symmetric=True)
    model = build_sgc([12, 4], k=2, dropout_rate=0.0)
    th = Trainer(model, ds, TrainConfig(features="host", **kw))
    td = Trainer(model, ds, TrainConfig(**kw))
    assert th.feats is None                  # never device-resident
    mh_, md_ = th.evaluate(), td.evaluate()
    np.testing.assert_allclose(mh_["train_loss"], md_["train_loss"],
                               rtol=1e-4)
    th.train(epochs=30)
    td.train(epochs=30)
    # same convergence; dropout=0 keeps the paths numerically aligned
    np.testing.assert_allclose(
        th.evaluate()["train_acc"], td.evaluate()["train_acc"],
        atol=0.05)
    assert th.evaluate()["train_acc"] > 0.9


def test_autopilot_selects_host_tier_for_sgc_over_budget():
    """A budget smaller than the feature matrix must route an SGC
    model to the host tier (VERDICT r4 weak #7: the out-of-core
    aggregator is now a plan the autopilot can SELECT, not shelf-ware)."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.sgc import build_sgc
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(4096, 6, in_dim=64, num_classes=4, seed=2)
    model = build_sgc([64, 4], k=1, dropout_rate=0.0)
    # 3 MB budget: [4096, 64] fp32 feats alone exceed 1 MB + tables
    tr = Trainer(model, ds, TrainConfig(
        verbose=False, eval_every=1 << 30, memory="auto",
        hbm_bytes=3 << 20))
    assert tr.config.features == "host"
    assert tr.feats is None
    tr.train(epochs=2)
    assert np.isfinite(tr.evaluate()["train_loss"])

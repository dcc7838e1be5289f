"""Block-dense MXU aggregation (ops/blockdense.py): plan + kernel
correctness against the segment-sum reference, occupancy accounting,
and the residual split's exactness."""

import numpy as np
import pytest

import jax.numpy as jnp

from roc_tpu.core.graph import planted_community_csr, random_csr
from roc_tpu.ops.aggregate import aggregate_segment
from roc_tpu.ops.blockdense import (BLOCK, aggregate_block_dense,
                                    plan_blocks)


def _reference(g, x):
    deg = np.diff(g.row_ptr)
    dst = np.repeat(np.arange(g.num_nodes, dtype=np.int64), deg)
    src, dstj = jnp.asarray(g.col_idx), jnp.asarray(dst)
    xp = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    return np.asarray(aggregate_segment(xp, src, dstj, g.num_nodes))


def _dense_plus_residual(g, x, plan):
    out = np.asarray(aggregate_block_dense(
        x, jnp.asarray(plan.a_blocks), jnp.asarray(plan.src_blk),
        jnp.asarray(plan.dst_blk), g.num_nodes, plan.vpad,
        chunk_blocks=4))
    # residual through the plain segment path
    res_deg = np.diff(plan.res_row_ptr)
    rdst = np.repeat(np.arange(g.num_nodes, dtype=np.int64), res_deg)
    if rdst.size:
        xp = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
        out = out + np.asarray(aggregate_segment(
            xp, jnp.asarray(plan.res_col), jnp.asarray(rdst),
            g.num_nodes))
    return out


@pytest.mark.parametrize("min_fill", [1, 8, 10**9])
def test_block_dense_plus_residual_matches_reference(min_fill):
    """dense tiles + residual CSR == the plain segment sum, at every
    split point (all-dense, mixed, all-residual)."""
    g = planted_community_csr(500, 6000, community_rows=BLOCK,
                              shuffle=False, seed=3)
    plan = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes,
                       min_fill=min_fill)
    assert plan.dense_edges + plan.res_col.shape[0] == g.num_edges
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(g.num_nodes, 24).astype(np.float32))
    got = _dense_plus_residual(g, x, plan)
    np.testing.assert_allclose(got, _reference(g, x), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("group", [2, 4, 7])
def test_grouped_reduction_matches_ungrouped(group):
    """pad_plan_groups + group>1 kernel == the group=1 result exactly
    in structure (same dense/residual split) and numerically (the
    padding blocks are zero-A): the output-RMW-traffic optimization
    must not change a single value."""
    from roc_tpu.ops.blockdense import pad_plan_groups
    g = planted_community_csr(500, 6000, community_rows=BLOCK,
                              shuffle=False, seed=3)
    plan = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes, min_fill=4)
    assert plan.n_blocks > 2
    padded = pad_plan_groups(plan, group)
    # group alignment, per-dst-tile padding only
    assert padded.n_blocks % group == 0
    assert padded.n_blocks < plan.n_blocks + group * len(
        np.unique(plan.dst_blk))
    # padding blocks are inert: zero A
    assert padded.a_blocks.sum() == plan.a_blocks.sum()
    # every group shares one dst tile
    dgrp = padded.dst_blk.reshape(-1, group)
    assert (dgrp == dgrp[:, :1]).all()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(g.num_nodes, 24).astype(np.float32))
    base = np.asarray(aggregate_block_dense(
        x, jnp.asarray(plan.a_blocks), jnp.asarray(plan.src_blk),
        jnp.asarray(plan.dst_blk), g.num_nodes, plan.vpad,
        chunk_blocks=4))
    got = np.asarray(aggregate_block_dense(
        x, jnp.asarray(padded.a_blocks), jnp.asarray(padded.src_blk),
        jnp.asarray(padded.dst_blk), g.num_nodes, padded.vpad,
        chunk_blocks=4 * group, group=group))
    np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)
    # an unpadded plan with group>1 must fail fast, not mis-aggregate
    if plan.n_blocks % group:
        with pytest.raises(ValueError, match="pad_plan_groups"):
            aggregate_block_dense(
                x, jnp.asarray(plan.a_blocks),
                jnp.asarray(plan.src_blk), jnp.asarray(plan.dst_blk),
                g.num_nodes, plan.vpad, group=group)


def test_trainer_bdense_group_matches_segment():
    """TrainConfig.bdense_group end-to-end through the Trainer:
    grouped bdense == ungrouped == segment (same trained params), with
    a real dense+residual split and real group padding exercised."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(300, 9, in_dim=12, num_classes=3, seed=4)
    kw = dict(learning_rate=0.05, epochs=5, eval_every=1 << 30,
              verbose=False, dropout_rate=0.0, symmetric=True)
    trainers = {}
    for label, impl, grp in (("segment", "segment", 1),
                             ("bdense", "bdense", 1),
                             ("bdense_g4", "bdense", 4)):
        tr = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                     TrainConfig(aggr_impl=impl, bdense_min_fill=40,
                                 bdense_group=grp, **kw))
        tr.train()
        trainers[label] = tr
    tg = trainers["bdense_g4"]
    assert tg.gctx.tables.group == 4
    assert tg.gctx.tables.a.shape[0] % 4 == 0
    # padding actually happened (the fixture's tile widths are odd)
    assert tg.gctx.tables.a.shape[0] > trainers["bdense"].gctx.tables.a.shape[0]
    for ref in ("bdense", "segment"):
        for k in trainers[ref].params:
            np.testing.assert_allclose(
                np.asarray(tg.params[k]),
                np.asarray(trainers[ref].params[k]),
                rtol=2e-4, atol=2e-4)


def test_plan_occupancy_reflects_structure():
    """Oracle-ordered community graph concentrates edges into few
    blocks; uniform random at the same V/E does not — the stat that
    decides whether the MXU path can win."""
    V, E = 2048, 60_000
    comm = planted_community_csr(V, E, community_rows=512,
                                 intra_frac=0.9, shuffle=False, seed=1)
    unif = random_csr(V, E, seed=1)
    po = plan_blocks(comm.row_ptr, comm.col_idx, V, min_fill=64)
    pu = plan_blocks(unif.row_ptr, unif.col_idx, V, min_fill=64)
    occ_o, occ_u = po.occupancy(), pu.occupancy()
    assert occ_o["dense_frac"] > 0.5
    # community order CONCENTRATES: fewer blocks, much higher fill
    assert occ_o["mean_fill"] > 2 * occ_u["mean_fill"]
    assert occ_o["n_blocks"] < occ_u["n_blocks"]
    # at large V a uniform graph scatters below any useful fill
    # (E * 128^2 / V^2 ~ 4 edges/block here)
    big = random_csr(20_000, 100_000, seed=2)
    pb = plan_blocks(big.row_ptr, big.col_idx, 20_000, min_fill=64)
    assert pb.occupancy()["dense_frac"] < 0.05


def test_duplicate_saturation_stays_exact():
    """Edges past uint8 multiplicity overflow to the residual CSR —
    total semantics stay exact."""
    # 400 copies of the same edge (0 <- 1) + a spread of others
    row_ptr = np.array([0, 400, 401, 402], dtype=np.int64)
    col_idx = np.array([1] * 400 + [2, 0], dtype=np.int64)
    from roc_tpu.core.graph import Graph
    g = Graph(row_ptr=row_ptr, col_idx=col_idx.astype(np.int32))
    plan = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes, min_fill=1)
    assert plan.res_col.shape[0] == 400 - 255  # saturated tail
    x = jnp.asarray(np.eye(3, 5, dtype=np.float32))
    got = _dense_plus_residual(g, x, plan)
    np.testing.assert_allclose(got, _reference(g, x), rtol=1e-5)


def test_numpy_fallback_rejects_out_of_range_cols():
    """The numpy plan path must hard-error on sources outside the
    declared tile space exactly like the native kErrValue path — a
    clamped gather would aggregate silently wrong."""
    import roc_tpu.native as native_mod
    ptr = np.array([0, 1, 2], dtype=np.int64)
    col = np.array([0, 300], dtype=np.int32)
    avail = native_mod.available
    native_mod.available = lambda: False
    try:
        with pytest.raises(ValueError, match="out of range"):
            plan_blocks(ptr, col, 2, min_fill=1, num_cols=200)
        # in-range passes
        plan_blocks(ptr, col, 2, min_fill=1, num_cols=400)
    finally:
        native_mod.available = avail


def test_empty_dense_plan():
    g = random_csr(300, 900, seed=0)
    plan = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes,
                       min_fill=10**9)
    assert plan.n_blocks == 0
    assert plan.res_col.shape[0] == g.num_edges


def test_a_budget_keeps_densest_blocks():
    """The A-table byte budget keeps the DENSEST qualifying blocks and
    exactness survives (the dropped blocks fall to the residual)."""
    g = planted_community_csr(600, 9000, community_rows=BLOCK,
                              shuffle=False, seed=5)
    full = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes, min_fill=1,
                       a_budget_bytes=None)
    budget = 2 * BLOCK * BLOCK  # room for exactly two blocks
    capped = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes, min_fill=1,
                         a_budget_bytes=budget)
    assert capped.n_blocks == 2 < full.n_blocks
    # the two kept blocks are the densest ones
    per_block_full = full.a_blocks.reshape(full.n_blocks, -1).sum(1)
    kept = np.sort(capped.a_blocks.reshape(2, -1).sum(1))
    assert (kept == np.sort(per_block_full)[-2:]).all()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(g.num_nodes, 8).astype(np.float32))
    np.testing.assert_allclose(_dense_plus_residual(g, x, capped),
                               _reference(g, x), rtol=1e-4, atol=1e-4)


def test_u4_packed_a_matches_uint8():
    """pack_a_u4 halves the A bytes and the kernel's in-register
    unpack reproduces the uint8 result exactly — grouped and
    ungrouped; plans with multiplicities past 4 bits must refuse to
    pack rather than saturate."""
    from roc_tpu.ops.blockdense import pack_a_u4
    g = planted_community_csr(500, 6000, community_rows=BLOCK,
                              shuffle=False, seed=3)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(g.num_nodes, 24).astype(np.float32))
    for group in (1, 4):
        plan = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes,
                           min_fill=4, group=group)
        assert plan.a_blocks.max() <= 15, "fixture must be packable"
        packed = pack_a_u4(plan)
        assert packed is not None
        assert packed.a_blocks.nbytes * 2 == plan.a_blocks.nbytes
        assert packed.occupancy()["a_bytes"] * 2 == \
            plan.occupancy()["a_bytes"]
        base = np.asarray(aggregate_block_dense(
            x, jnp.asarray(plan.a_blocks), jnp.asarray(plan.src_blk),
            jnp.asarray(plan.dst_blk), g.num_nodes, plan.vpad,
            chunk_blocks=4 * group, group=group))
        got = np.asarray(aggregate_block_dense(
            x, jnp.asarray(packed.a_blocks),
            jnp.asarray(packed.src_blk), jnp.asarray(packed.dst_blk),
            g.num_nodes, packed.vpad,
            chunk_blocks=4 * group, group=group))
        np.testing.assert_array_equal(got, base)
    # >15 multiplicity: refuse to pack (the 400-duplicate fixture)
    from roc_tpu.core.graph import Graph
    row_ptr = np.array([0, 400, 401, 402], dtype=np.int64)
    col_idx = np.array([1] * 400 + [2, 0], dtype=np.int32)
    gd = Graph(row_ptr=row_ptr, col_idx=col_idx)
    pd = plan_blocks(gd.row_ptr, gd.col_idx, gd.num_nodes, min_fill=1)
    assert pd.a_blocks.max() > 15
    assert pack_a_u4(pd) is None


def test_plan_blocks_packed_budget_policy():
    """plan_blocks_packed spends the stated budget in DEVICE bytes:
    a packable graph keeps ~2x the blocks a uint8 plan could (packed
    bytes still <= budget); an unpackable graph re-plans to the uint8
    cap rather than exceeding it."""
    from roc_tpu.ops.blockdense import plan_blocks_packed
    g = planted_community_csr(600, 9000, community_rows=BLOCK,
                              shuffle=False, seed=5)
    budget = 2 * BLOCK * BLOCK  # two uint8 blocks / four packed
    p8 = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes, min_fill=1,
                     a_budget_bytes=budget)
    pp = plan_blocks_packed(g.row_ptr, g.col_idx, g.num_nodes,
                            min_fill=1, a_budget_bytes=budget)
    assert pp.a_blocks.shape[-1] == BLOCK // 2, "fixture packable"
    assert pp.a_blocks.nbytes <= budget
    assert pp.n_blocks == 2 * p8.n_blocks
    # unpackable: the 400-duplicate fixture must land at uint8 <= cap
    from roc_tpu.core.graph import Graph
    row_ptr = np.array([0, 400, 401, 402], dtype=np.int64)
    col_idx = np.array([1] * 400 + [2, 0], dtype=np.int32)
    gd = Graph(row_ptr=row_ptr, col_idx=col_idx)
    pu = plan_blocks_packed(gd.row_ptr, gd.col_idx, gd.num_nodes,
                            min_fill=1,
                            a_budget_bytes=BLOCK * BLOCK)
    assert pu.a_blocks.shape[-1] == BLOCK  # uint8
    assert pu.a_blocks.nbytes <= BLOCK * BLOCK


def test_probe_dense_frac_matches_plan():
    """The census-only auto probe must agree with the full plan's
    dense_frac (same census + same selection, minus the A fill)."""
    import roc_tpu.native as native_mod
    if not native_mod.available():
        pytest.skip("probe is native-gated")
    from roc_tpu.ops.blockdense import probe_dense_frac
    comm = planted_community_csr(2048, 60_000, community_rows=512,
                                 intra_frac=0.9, shuffle=False, seed=1)
    unif = random_csr(20_000, 100_000, seed=2)
    for g, v in ((comm, 2048), (unif, 20_000)):
        frac = probe_dense_frac(g.row_ptr, g.col_idx, v, min_fill=64)
        plan = plan_blocks(g.row_ptr, g.col_idx, v, min_fill=64)
        assert frac == pytest.approx(plan.occupancy()["dense_frac"],
                                     abs=1e-3)
    # grouped probe respects the padded-budget selection
    budget = 4 * BLOCK * BLOCK
    frac_b = probe_dense_frac(comm.row_ptr, comm.col_idx, 2048,
                              min_fill=1, a_budget_bytes=budget,
                              group=4)
    plan_b = plan_blocks(comm.row_ptr, comm.col_idx, 2048, min_fill=1,
                         a_budget_bytes=budget, group=4)
    assert frac_b == pytest.approx(plan_b.occupancy()["dense_frac"],
                                   abs=1e-3)


def test_auto_impl_probes_structure(monkeypatch):
    """aggr_impl='auto' switches to bdense when the census finds
    enough dense-tile structure, and stays sectioned on a uniform
    graph — the flagship path must be reachable without naming it."""
    import roc_tpu.native as native_mod
    if not native_mod.available():
        pytest.skip("probe is native-gated")
    from roc_tpu.core import ell as ell_mod
    from roc_tpu.core.graph import Dataset
    from roc_tpu.ops import blockdense as bd
    from roc_tpu.train.trainer import make_graph_context

    # shrink the gate sizes so the fixture stays test-sized; the
    # trainer reads both dynamically
    monkeypatch.setattr(bd, "BDENSE_AUTO_MIN_EDGES", 10_000)
    monkeypatch.setattr(ell_mod, "SECTIONED_BOUNDS_DEFAULT",
                        (1_000, 10**9), raising=False)
    monkeypatch.setattr(ell_mod, "sectioned_bounds",
                        lambda device_kind=None: (1_000, 10**9))

    def mk(g):
        rng = np.random.RandomState(0)
        return Dataset(graph=g,
                       features=rng.rand(g.num_nodes, 8).astype(
                           np.float32),
                       labels=np.zeros(g.num_nodes, np.int32),
                       mask=np.ones(g.num_nodes, np.int32),
                       num_classes=2, name="probe")

    comm = planted_community_csr(2048, 60_000, community_rows=512,
                                 intra_frac=0.9, shuffle=False, seed=1)
    gc = make_graph_context(mk(comm), "auto", bdense_min_fill=64)
    assert gc.aggr_impl == "bdense"
    assert gc.tables.a is not None
    unif = random_csr(20_000, 100_000, seed=2)
    gu = make_graph_context(mk(unif), "auto", bdense_min_fill=64)
    assert gu.aggr_impl == "sectioned"

    # the shared resolver: census returned on the bdense path is
    # byte-identical to a fresh plan's walk; multiprocess runs skip
    # the probe (per-host native availability must not desync SPMD)
    from roc_tpu.train.trainer import resolve_auto_impl_probed
    impl, census = resolve_auto_impl_probed(comm, bdense_min_fill=64)
    assert impl == "bdense" and census is not None
    p_census = plan_blocks(comm.row_ptr, comm.col_idx, 2048,
                           min_fill=64, census=census)
    p_fresh = plan_blocks(comm.row_ptr, comm.col_idx, 2048,
                          min_fill=64)
    np.testing.assert_array_equal(p_census.a_blocks, p_fresh.a_blocks)
    np.testing.assert_array_equal(p_census.res_col, p_fresh.res_col)
    impl_mp, cen_mp = resolve_auto_impl_probed(
        comm, bdense_min_fill=64, multiprocess=True)
    assert impl_mp == "sectioned" and cen_mp is None


def test_auto_probe_without_native_stays_sectioned(monkeypatch):
    """No librocio -> the probe declines (None) and 'auto' keeps the
    arithmetic resolution — never the minutes-long numpy census."""
    import roc_tpu.native as native_mod
    from roc_tpu.core import ell as ell_mod
    from roc_tpu.ops import blockdense as bd
    from roc_tpu.train.trainer import resolve_auto_impl_probed
    monkeypatch.setattr(native_mod, "available", lambda: False)
    monkeypatch.setattr(bd, "BDENSE_AUTO_MIN_EDGES", 10_000)
    monkeypatch.setattr(ell_mod, "sectioned_bounds",
                        lambda device_kind=None: (1_000, 10**9))
    comm = planted_community_csr(2048, 60_000, community_rows=512,
                                 intra_frac=0.9, shuffle=False, seed=1)
    impl, census = resolve_auto_impl_probed(comm, bdense_min_fill=64)
    assert impl == "sectioned" and census is None


def test_group_padding_respects_a_budget():
    """With group>1 the budget caps the PADDED table: the selection
    must account for alignment blocks up front, never exceed the byte
    cap after padding, and exactness must survive (dropped blocks fall
    to the residual)."""
    g = planted_community_csr(600, 9000, community_rows=BLOCK,
                              shuffle=False, seed=5)
    budget = 4 * BLOCK * BLOCK  # room for four PADDED blocks
    plan = plan_blocks(g.row_ptr, g.col_idx, g.num_nodes, min_fill=1,
                       a_budget_bytes=budget, group=4)
    assert plan.n_blocks * BLOCK * BLOCK <= budget
    assert plan.n_blocks % 4 == 0
    # group=1 at the same budget keeps 4 raw blocks; grouping must
    # not keep MORE raw blocks than that
    raw = plan.n_blocks - plan.pad_blocks
    assert 0 < raw <= 4
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(g.num_nodes, 8).astype(np.float32))
    out = np.asarray(aggregate_block_dense(
        x, jnp.asarray(plan.a_blocks), jnp.asarray(plan.src_blk),
        jnp.asarray(plan.dst_blk), g.num_nodes, plan.vpad,
        chunk_blocks=4, group=4))
    res_deg = np.diff(plan.res_row_ptr)
    rdst = np.repeat(np.arange(g.num_nodes, dtype=np.int64), res_deg)
    if rdst.size:
        xp = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
        out = out + np.asarray(aggregate_segment(
            xp, jnp.asarray(plan.res_col), jnp.asarray(rdst),
            g.num_nodes))
    np.testing.assert_allclose(out, _reference(g, x), rtol=1e-4,
                               atol=1e-4)


def test_trainer_bdense_matches_segment():
    """aggr_impl='bdense' end-to-end through the Trainer: identical
    training trajectory to the segment reference.  bdense_min_fill=250
    forces a REAL dense+residual split (4 dense tiles, 718 residual
    edges on this fixture) so the trainer's sectioned-residual glue is
    exercised, not just the all-dense fast case."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(300, 9, in_dim=12, num_classes=3, seed=4)
    kw = dict(learning_rate=0.05, epochs=5, eval_every=1 << 30,
              verbose=False, dropout_rate=0.0, symmetric=True)
    tb = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                 TrainConfig(aggr_impl="bdense", bdense_min_fill=250,
                             **kw))
    # the plan actually split: dense tiles AND a sectioned residual
    assert tb.gctx.tables.a is not None and tb.gctx.tables.a.shape[0] > 0
    assert tb.gctx.tables.residual.idx, "fixture must leave residual edges"
    ts = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                 TrainConfig(aggr_impl="segment", **kw))
    tb.train()
    ts.train()
    for k in ts.params:
        np.testing.assert_allclose(np.asarray(tb.params[k]),
                                   np.asarray(ts.params[k]),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(tb.evaluate()["train_loss"],
                               ts.evaluate()["train_loss"], rtol=1e-4)


def test_trainer_bdense_no_dense_tiles_falls_back():
    """A graph/order with no qualifying tile runs the pure sectioned
    residual (no zero-block kernel in the step) and still matches the
    segment reference."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(300, 9, in_dim=12, num_classes=3, seed=4)
    kw = dict(learning_rate=0.05, epochs=3, eval_every=1 << 30,
              verbose=False, dropout_rate=0.0, symmetric=True)
    tb = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                 TrainConfig(aggr_impl="bdense",
                             bdense_min_fill=10**9, **kw))
    assert tb.gctx.tables.a is None
    assert tb.gctx.tables.residual.idx
    ts = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                 TrainConfig(aggr_impl="segment", **kw))
    tb.train()
    ts.train()
    for k in ts.params:
        np.testing.assert_allclose(np.asarray(tb.params[k]),
                                   np.asarray(ts.params[k]),
                                   rtol=2e-4, atol=2e-4)


def test_trainer_bdense_mixed_precision_converges():
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(300, 9, in_dim=12, num_classes=3, seed=4)
    tr = Trainer(build_gcn([12, 16, 3], dropout_rate=0.0), ds,
                 TrainConfig(aggr_impl="bdense", learning_rate=0.05,
                             epochs=60, eval_every=1 << 30,
                             verbose=False, symmetric=True,
                             compute_dtype=jnp.bfloat16))
    tr.train()
    m = tr.evaluate()
    assert np.isfinite(m["train_loss"])
    assert m["train_acc"] > 0.9


def test_bdense_distributed_matches_segment():
    """aggr_impl='bdense' through the DistributedTrainer (per-partition
    rectangular plans: local dst rows x gathered source coords): same
    training trajectory as the distributed segment reference, with a
    REAL dense+residual split on at least one partition."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(384, 9, in_dim=12, num_classes=3, seed=4)
    kw = dict(learning_rate=0.05, epochs=5, eval_every=1 << 30,
              verbose=False, dropout_rate=0.0, symmetric=True)
    tb = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4,
                            TrainConfig(aggr_impl="bdense",
                                        bdense_min_fill=64, **kw))
    # the per-part plans actually split: dense tiles AND residuals
    assert tb.data.tables.a is not None, "fixture must yield dense tiles"
    dense_total = sum(o["dense_edges"] for o in tb.data.bd_occupancy)
    # a REAL residual remains (counted off the plans, not read off
    # the residual tables' presence)
    assert 0 < dense_total < ds.graph.num_edges, dense_total
    assert tb.data.tables.residual.idx
    assert tb.data.tables.src_vpad >= 4 * tb.pg.part_nodes
    ts = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4, TrainConfig(aggr_impl="segment",
                                               **kw))
    tb.train()
    ts.train()
    for k in ts.params:
        np.testing.assert_allclose(np.asarray(tb.params[k]),
                                   np.asarray(ts.params[k]),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(tb.evaluate()["train_loss"],
                               ts.evaluate()["train_loss"], rtol=1e-4)
    # predict rides the same tables
    np.testing.assert_allclose(tb.predict(), ts.predict(),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("group", [1, 4])
def test_bdense_distributed_matches_single_device(group):
    """1-vs-N invariance for the bdense path: the 4-part distributed
    run reproduces the single-device bdense trajectory — with and
    without the grouped output-tile reduction (whose per-part
    alignment + whole-group stacked tail padding is the subtle SPMD
    invariant)."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(384, 9, in_dim=12, num_classes=3, seed=4)
    kw = dict(learning_rate=0.05, epochs=4, eval_every=1 << 30,
              verbose=False, dropout_rate=0.0, symmetric=True,
              bdense_group=group)
    td = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4,
                            TrainConfig(aggr_impl="bdense",
                                        bdense_min_fill=64, **kw))
    t1 = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                 TrainConfig(aggr_impl="bdense", bdense_min_fill=64,
                             **kw))
    if group > 1:
        assert td.data.tables.group == group
        assert td.data.tables.a.shape[1] % group == 0
    td.train()
    t1.train()
    for k in t1.params:
        np.testing.assert_allclose(np.asarray(td.params[k]),
                                   np.asarray(t1.params[k]),
                                   rtol=2e-4, atol=2e-4)


def test_bdense_distributed_packs_with_zero_block_parts():
    """A packable graph where some partitions plan ZERO dense tiles
    must still stack the u4 table (a zero-block part's empty A packs
    to the uniform trailing width instead of forcing uint8 or
    crashing the stack) and train exactly."""
    from roc_tpu.core.graph import Dataset, from_edge_list
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    rng = np.random.RandomState(5)
    V = 256
    # one CONCENTRATED community (rows 0-63: ~1500 edges in a single
    # tile, far past min_fill) + SCATTERED edges over the rest (fill
    # per tile ~50, under min_fill): the edge-balanced partitioner
    # gives every part similar edge counts, but only the parts
    # holding community rows plan dense tiles
    dense_s = rng.randint(0, 64, 4000)
    dense_d = rng.randint(0, 64, 4000)
    scat_s = rng.randint(0, V, 300)
    scat_d = rng.randint(64, V, 300)
    src = np.concatenate([dense_s, scat_s, np.arange(V)])
    dst = np.concatenate([dense_d, scat_d, np.arange(V)])
    g = from_edge_list(src, dst, V)
    ds = Dataset(graph=g,
                 features=rng.rand(V, 8).astype(np.float32),
                 labels=rng.randint(0, 3, V).astype(np.int32),
                 mask=np.ones(V, np.int32), num_classes=3)
    kw = dict(verbose=False, eval_every=1 << 30, dropout_rate=0.0,
              symmetric=False, epochs=2, learning_rate=0.05,
              chunk=64)   # partition geometry the fixture's split
    td = DistributedTrainer(build_gcn([8, 8, 3], dropout_rate=0.0),
                            ds, 4,
                            TrainConfig(aggr_impl="bdense",
                                        bdense_min_fill=300, **kw))
    occ = td.data.bd_occupancy
    assert any(o["n_blocks"] == 0 for o in occ), \
        "fixture must leave some partition without dense tiles"
    assert any(o["n_blocks"] > 0 for o in occ)
    assert td.data.tables.a.shape[-1] == 64  # u4 despite empties
    ts = DistributedTrainer(build_gcn([8, 8, 3], dropout_rate=0.0),
                            ds, 4, TrainConfig(aggr_impl="segment",
                                               **kw))
    td.train()
    ts.train()
    for k in ts.params:
        np.testing.assert_allclose(np.asarray(td.params[k]),
                                   np.asarray(ts.params[k]),
                                   rtol=2e-4, atol=2e-4)


def test_bdense_distributed_unpackable_stays_uint8_and_exact():
    """A >15-multiplicity graph must stack uint8 tables (no silent
    saturation) and still train to the segment reference."""
    from roc_tpu.core.graph import Dataset, Graph
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    rng = np.random.RandomState(3)
    V = 64
    # a hub destination with 40 copies of one source edge (mult > 15)
    src = np.concatenate([np.full(40, 7), rng.randint(0, V, 400),
                          np.arange(V)]).astype(np.int64)
    dst = np.concatenate([np.full(40, 3), rng.randint(0, V, 400),
                          np.arange(V)]).astype(np.int64)
    from roc_tpu.core.graph import from_edge_list
    g = from_edge_list(src, dst, V)
    ds = Dataset(graph=g,
                 features=rng.rand(V, 8).astype(np.float32),
                 labels=rng.randint(0, 3, V).astype(np.int32),
                 mask=np.ones(V, np.int32), num_classes=3)
    cfg = TrainConfig(aggr_impl="bdense", bdense_min_fill=1,
                      verbose=False, eval_every=1 << 30,
                      dropout_rate=0.0, symmetric=False, epochs=2,
                      learning_rate=0.05)
    td = DistributedTrainer(build_gcn([8, 8, 3], dropout_rate=0.0),
                            ds, 4, cfg)
    assert td.data.tables.a.shape[-1] == 128  # uint8, not packed
    ts = DistributedTrainer(build_gcn([8, 8, 3], dropout_rate=0.0),
                            ds, 4,
                            TrainConfig(aggr_impl="segment",
                                        verbose=False,
                                        eval_every=1 << 30,
                                        dropout_rate=0.0,
                                        symmetric=False, epochs=2,
                                        learning_rate=0.05))
    td.train()
    ts.train()
    for k in ts.params:
        np.testing.assert_allclose(np.asarray(td.params[k]),
                                   np.asarray(ts.params[k]),
                                   rtol=2e-4, atol=2e-4)


def test_bdense_distributed_group_mismatch_fails_fast():
    """Injected data built with one bdense_group must be rejected by a
    config wanting another — a silent mismatch would reduce across
    dst-tile boundaries without any shape error."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.core.partition import partition_graph
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel import multihost as mh
    from roc_tpu.parallel.distributed import (DistributedTrainer,
                                              shard_dataset)
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=2)
    pg = partition_graph(ds.graph, 4, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(4)
    data = shard_dataset(ds, pg, mesh, aggr_impl="bdense",
                         bdense_min_fill=8)  # group=1 tables
    assert data.tables.a is not None and data.tables.group == 1
    with pytest.raises(ValueError, match="bdense_group"):
        DistributedTrainer(
            build_gcn([12, 8, 3], dropout_rate=0.0), ds, 4,
            TrainConfig(aggr_impl="bdense", bdense_min_fill=8,
                        bdense_group=4, verbose=False),
            mesh=mesh, data=data, pg=pg)


def test_bdense_distributed_no_dense_tiles_falls_back():
    """min_fill too high for any partition: pure sectioned residual,
    no zero-block kernel in the step."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(384, 9, in_dim=12, num_classes=3, seed=4)
    kw = dict(learning_rate=0.05, epochs=2, eval_every=1 << 30,
              verbose=False, dropout_rate=0.0, symmetric=True)
    tb = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4,
                            TrainConfig(aggr_impl="bdense",
                                        bdense_min_fill=10**9, **kw))
    assert tb.data.tables.a is None
    assert tb.data.tables.residual.idx
    ts = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4, TrainConfig(aggr_impl="segment",
                                               **kw))
    tb.train()
    ts.train()
    for k in ts.params:
        np.testing.assert_allclose(np.asarray(tb.params[k]),
                                   np.asarray(ts.params[k]),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("group", [1, 3])
def test_bdense_multihost_local_build_matches_global_and_trains(group):
    """shard_dataset_local's bdense tables (block-count + residual
    chunk plan agreed via the O(P) collectives) must equal
    shard_dataset's single-controller build — including the group
    alignment, whose uniform stacked tail relies on every host's
    count being a group multiple — and the injected-data path must
    train through them."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.core.partition import partition_graph
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel import multihost as mh
    from roc_tpu.parallel.distributed import (DistributedTrainer,
                                              shard_dataset)
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=2)
    pg = partition_graph(ds.graph, 4, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(4)
    kw = dict(aggr_impl="bdense", bdense_min_fill=8,
              bdense_group=group)
    loc = mh.shard_dataset_local(ds, pg, mesh, **kw)
    glo = shard_dataset(ds, pg, mesh, **kw)
    lt, gt = loc.tables, glo.tables
    assert lt.a is not None and gt.a is not None, \
        "fixture must yield dense tiles in both builders"
    assert lt.group == group == gt.group
    # the packable fixture stacks u4 tables in BOTH builders (the
    # multihost packing decision rides the max-multiplicity
    # collective; a width mismatch here means the agreement broke)
    assert lt.a.shape[-1] == 64 == gt.a.shape[-1]
    for a, b in zip((lt.a, lt.src, lt.dst), (gt.a, gt.src, gt.dst)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (lt.vpad, lt.src_vpad) == (gt.vpad, gt.src_vpad)
    for a, b in zip(lt.residual.idx, gt.residual.idx):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(lt.residual.sub_dst, gt.residual.sub_dst):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert lt.residual.meta == gt.residual.meta
    cfg = TrainConfig(epochs=2, verbose=False, aggr_impl="bdense",
                      bdense_min_fill=8, bdense_group=group,
                      dropout_rate=0.0, eval_every=1 << 30)
    tr = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4, cfg, mesh=mesh, data=loc, pg=pg)
    tr.train(epochs=2)
    assert np.isfinite(tr.evaluate()["train_loss"])


def test_trainer_bdense_a_budget_caps_plan_and_stays_exact():
    """TrainConfig.bdense_a_budget reaches the planner and caps
    DEVICE bytes: a one-uint8-block budget holds TWO u4-packed blocks
    on this (packable) fixture, shrinks the plan vs uncapped, pushes
    the dropped blocks into the sectioned residual, and the capped
    trainer still matches the segment reference exactly."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer

    ds = synthetic_dataset(300, 9, in_dim=12, num_classes=3, seed=4)
    kw = dict(learning_rate=0.05, epochs=4, eval_every=1 << 30,
              verbose=False, dropout_rate=0.0, symmetric=True)
    uncapped = Trainer(
        build_gcn([12, 8, 3], dropout_rate=0.0), ds,
        TrainConfig(aggr_impl="bdense", bdense_min_fill=250,
                    bdense_a_budget=None, **kw))
    capped = Trainer(
        build_gcn([12, 8, 3], dropout_rate=0.0), ds,
        TrainConfig(aggr_impl="bdense", bdense_min_fill=250,
                    bdense_a_budget=128 * 128, **kw))
    n_unc = int(uncapped.gctx.tables.a.shape[0])
    assert n_unc > 2, "fixture must yield multiple dense tiles"
    assert int(capped.gctx.tables.a.shape[0]) == 2
    assert capped.gctx.tables.a.shape[-1] == 64  # u4-packed
    assert capped.gctx.tables.a.size <= 128 * 128  # device bytes <= cap
    ref = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                  TrainConfig(aggr_impl="segment", **kw))
    capped.train()
    ref.train()
    for k in ref.params:
        np.testing.assert_allclose(np.asarray(capped.params[k]),
                                   np.asarray(ref.params[k]),
                                   rtol=2e-4, atol=2e-4)

"""Multi-host runtime tests on the 8-virtual-device CPU rig.

Single-process is the degenerate case of every multihost helper, so
these validate the mesh layout, local-part selection, per-shard array
assembly, and that DistributedTrainer runs unchanged on
``shard_dataset_local`` output."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.core.partition import partition_graph
from roc_tpu.models.gcn import build_gcn
from roc_tpu.parallel import multihost as mh


def test_init_distributed_noop_without_coordinator(monkeypatch):
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    mh.init_distributed()  # must not raise or initialize anything


def test_make_parts_mesh_defaults():
    mesh = mh.make_parts_mesh()
    assert mesh.axis_names == ("parts",)
    assert mesh.devices.size == len(jax.devices())
    small = mh.make_parts_mesh(4)
    assert small.devices.size == 4


def test_process_local_parts_single_process():
    mesh = mh.make_parts_mesh(8)
    assert mh.process_local_parts(mesh) == list(range(8))


def test_make_sharded_array_roundtrip():
    mesh = mh.make_parts_mesh(4)
    data = np.arange(4 * 3 * 2, dtype=np.float32).reshape(4, 3, 2)
    local = mh.process_local_parts(mesh)
    arr = mh.make_sharded_array(mesh, local,
                                [data[p:p + 1] for p in local],
                                data.shape)
    assert arr.shape == data.shape
    np.testing.assert_array_equal(np.asarray(arr), data)
    # each shard actually lives on its mesh device
    shards = {s.device: np.asarray(s.data) for s in arr.addressable_shards}
    for i, d in enumerate(mesh.devices.reshape(-1)):
        np.testing.assert_array_equal(shards[d], data[i:i + 1])


def test_local_ell_plan_matches_global_on_full_part():
    """Regression (round-2 advisor, high): when real_nodes[p] ==
    part_nodes, padding edges inflate the last real row's local-CSR
    degree; the shape plan must be derived from those SAME degrees or
    the local ELL tables silently drop that row's edges and diverge
    from shard_dataset's.

    Since the cost-partitioning PR the plan layer PREVENTS the
    hazardous fixture outright: a part whose real rows exactly fill
    part_nodes while carrying padding edges gets one extra
    row-multiple (core/partition.plan_from_bounds), because the
    sectioned/bdense planners — unlike the ELL builder this test
    originally pinned — cannot tolerate dummy sources inside real
    rows.  The test now asserts that invariant AND keeps the
    local-vs-global ELL equality on the same node_multiple=1
    fixture."""
    from roc_tpu.parallel.distributed import shard_dataset

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=11)
    # node_multiple=1: the largest partition WOULD be exactly full —
    # the plan layer must have padded it by one extra row-multiple
    # instead of letting its last real row absorb the padding edges
    pg = partition_graph(ds.graph, 4, node_multiple=1, edge_multiple=128)
    full = np.flatnonzero(pg.real_nodes == pg.part_nodes)
    assert not full.size, (
        "plan_from_bounds must keep padding edges on padded rows — a "
        "full partition with padding edges leaks dummy sources into "
        "real rows")
    assert pg.part_nodes == int(pg.real_nodes.max()) + 1

    mesh = mh.make_parts_mesh(4)
    loc = mh.shard_dataset_local(ds, pg, mesh, aggr_impl="ell")
    glo = shard_dataset(ds, pg, mesh, aggr_impl="ell")
    assert len(loc.ell_idx) == len(glo.ell_idx)
    for a, b in zip(loc.ell_idx, glo.ell_idx):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(loc.ell_row_pos),
                                  np.asarray(glo.ell_row_pos))
    # the attention row map must agree too (EllTable.row_id)
    assert len(loc.ell_row_id) == len(glo.ell_row_id)
    for a, b in zip(loc.ell_row_id, glo.ell_row_id):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_predict_on_local_shards():
    """predict() (replicated all_gather output) returns the same
    original-order logits from partition-local shards as from the
    global build."""
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=7)
    mesh = mh.make_parts_mesh(4)
    cfg = TrainConfig(verbose=False, aggr_impl="ell", symmetric=True)
    tr = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4, cfg, mesh=mesh)
    want = tr.predict()
    assert want.shape == (96, 3)
    tr.data = mh.shard_dataset_local(ds, tr.pg, mesh, aggr_impl="ell")
    # atol: the global build carries baked fused-norm weight tables,
    # the local-shards build scales in-op (same operator, different
    # fp32 association) — near-zero logits need an absolute floor
    np.testing.assert_allclose(tr.predict(), want, rtol=1e-5,
                               atol=1e-6)


def test_gat_trains_on_local_shards():
    """Attention over partition-local ELL tables: the multihost
    row_id upload must feed the edge softmax identically to the
    global path."""
    from roc_tpu.models.gat import build_gat
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=5)
    mesh = mh.make_parts_mesh(4)
    cfg = TrainConfig(epochs=2, verbose=False, aggr_impl="ell",
                      symmetric=True, dropout_rate=0.0)
    tr = DistributedTrainer(build_gat([12, 8, 3], dropout_rate=0.0),
                            ds, 4, cfg, mesh=mesh)
    want = tr.evaluate()["train_loss"]
    tr.data = mh.shard_dataset_local(ds, tr.pg, mesh, aggr_impl="ell")
    got = tr.evaluate()["train_loss"]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    tr.train(epochs=2)
    assert np.isfinite(tr.evaluate()["train_loss"])


@pytest.mark.parametrize("halo", ["gather", "ring"])
def test_distributed_trainer_on_local_shards(halo):
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    n_dev = 4
    ds = synthetic_dataset(16 * n_dev, 6, in_dim=12, num_classes=3,
                           seed=0)
    mesh = mh.make_parts_mesh(n_dev)
    cfg = TrainConfig(epochs=2, verbose=False, aggr_impl="sectioned",
                      chunk=64, halo=halo)
    tr = DistributedTrainer(build_gcn([12, 8, 3]), ds, n_dev, cfg,
                            mesh=mesh)
    pg = partition_graph(ds.graph, n_dev)
    tr.data = mh.shard_dataset_local(ds, tr.pg, mesh,
                                     dtype=jnp.float32,
                                     aggr_impl="sectioned", halo=halo)
    tr.train(epochs=2)
    m = tr.evaluate()
    assert np.isfinite(m["train_loss"])


@pytest.mark.parametrize("impl", ["ell", "bdense"])
def test_two_process_dcn_parity(tmp_path, impl):
    """REAL 2-process execution (VERDICT r4 missing #3): two OS
    processes x 4 CPU devices meet via jax.distributed.initialize,
    each builds only its own partitions with shard_dataset_local,
    trains 2 epochs with cross-process psum, and the result must match
    a single-process run of the identical 8-part workload bit-for-bit
    up to float tolerance.  The bdense variant exercises the REAL
    cross-process block-count/chunk-plan agreement collectives."""
    import socket
    import subprocess
    import sys as _sys
    import os as _os

    worker = _os.path.join(_os.path.dirname(__file__),
                           "multihost_worker.py")
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = dict(_os.environ)
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + _os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [_sys.executable, worker, f"localhost:{port}", "2", str(i),
         str(tmp_path), impl],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-3000:]
        assert "WORKER_OK" in out
    got = np.load(tmp_path / "result.npz")

    # identical workload, single process on the in-test 8-device rig
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig
    ds = synthetic_dataset(16 * 8, 6, in_dim=12, num_classes=3, seed=0)
    mesh = mh.make_parts_mesh(8)
    cfg = TrainConfig(epochs=2, verbose=False, aggr_impl=impl,
                      bdense_min_fill=8,
                      symmetric=True, dropout_rate=0.0,
                      eval_every=1 << 30)
    tr = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 8, cfg, mesh=mesh)
    tr.train(epochs=2)
    want_m = tr.evaluate()
    np.testing.assert_allclose(got["train_loss"], want_m["train_loss"],
                               rtol=1e-5)
    np.testing.assert_allclose(got["train_acc"], want_m["train_acc"],
                               rtol=1e-6)
    for k, v in tr.params.items():
        np.testing.assert_allclose(got[f"param_{k}"], np.asarray(v),
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got["logits"], tr.predict(),
                               rtol=2e-4, atol=2e-4)


def test_local_sectioned_honors_sub_w_and_u16():
    """shard_dataset_local must honor sect_sub_w/sect_u16 exactly like
    shard_dataset (the advisor's silently-dropped-config class, fixed
    at BOTH levels)."""
    from roc_tpu.parallel.distributed import shard_dataset

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=11)
    pg = partition_graph(ds.graph, 4, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(4)
    loc = mh.shard_dataset_local(ds, pg, mesh, aggr_impl="sectioned",
                                 sect_sub_w=16, sect_u16=True)
    glo = shard_dataset(ds, pg, mesh, aggr_impl="sectioned",
                        sect_sub_w=16, sect_u16=True)
    assert len(loc.sect_idx) == len(glo.sect_idx)
    for a, b in zip(loc.sect_idx, glo.sect_idx):
        assert a.dtype == jnp.uint16
        assert a.shape[-1] == 16
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(loc.sect_sub_dst, glo.sect_sub_dst):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert loc.sect_meta == glo.sect_meta


def test_local_flat8_matches_global_and_trains():
    """shard_dataset_local's attn_flat8 tables must equal
    shard_dataset's, and the injected-data path must run the GAT
    through them (the multi-host large-attention entry point)."""
    from roc_tpu.models.gat import build_gat
    from roc_tpu.parallel.distributed import (DistributedTrainer,
                                              shard_dataset)
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=11)
    pg = partition_graph(ds.graph, 4, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(4)
    loc = mh.shard_dataset_local(ds, pg, mesh, aggr_impl="attn_flat8")
    glo = shard_dataset(ds, pg, mesh, aggr_impl="attn_flat8")
    assert len(loc.sect_idx) == 1 == len(glo.sect_idx)
    np.testing.assert_array_equal(np.asarray(loc.sect_idx[0]),
                                  np.asarray(glo.sect_idx[0]))
    np.testing.assert_array_equal(np.asarray(loc.sect_sub_dst[0]),
                                  np.asarray(glo.sect_sub_dst[0]))
    # the flat edge arrays are stubs, not [P, E_p] uploads
    assert loc.edge_src.shape[-1] == 1
    cfg = TrainConfig(epochs=2, verbose=False, aggr_impl="attn_flat8",
                      dropout_rate=0.0, eval_every=1 << 30)
    tr = DistributedTrainer(build_gat([12, 8, 3], dropout_rate=0.0),
                            ds, 4, cfg, mesh=mesh, data=loc, pg=pg)
    tr.train(epochs=2)
    assert np.isfinite(tr.evaluate()["train_loss"])


def test_local_flat_sum_matches_global_and_trains():
    """shard_dataset_local's flat_sum tables must equal
    shard_dataset's and train through the injected-data path — the
    resolve pass auto-routes multi-process >=20M-edge configs to
    flat_sum, so the multihost builder must host it (parity vs the
    single-device segment reference <= 1e-5)."""
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import (DistributedTrainer,
                                              shard_dataset)
    from roc_tpu.train.trainer import Trainer, TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=11)
    pg = partition_graph(ds.graph, 4, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(4)
    loc = mh.shard_dataset_local(ds, pg, mesh, aggr_impl="flat_sum")
    glo = shard_dataset(ds, pg, mesh, aggr_impl="flat_sum")
    assert len(loc.sect_idx) == 1 == len(glo.sect_idx)
    np.testing.assert_array_equal(np.asarray(loc.sect_idx[0]),
                                  np.asarray(glo.sect_idx[0]))
    np.testing.assert_array_equal(np.asarray(loc.sect_sub_dst[0]),
                                  np.asarray(glo.sect_sub_dst[0]))
    # the flat edge arrays are stubs, not [P, E_p] uploads
    assert loc.edge_src.shape[-1] == 1
    cfg = TrainConfig(epochs=3, verbose=False, aggr_impl="flat_sum",
                      symmetric=True, dropout_rate=0.0,
                      eval_every=1 << 30)
    tr = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, 4, cfg, mesh=mesh, data=loc, pg=pg)
    tr.train(epochs=3)
    ref = Trainer(build_gcn([12, 8, 3], dropout_rate=0.0), ds,
                  TrainConfig(epochs=3, verbose=False,
                              aggr_impl="segment", symmetric=True,
                              dropout_rate=0.0, eval_every=1 << 30))
    ref.train(epochs=3)
    p0 = np.asarray(ref.predict(), np.float64)
    p1 = np.asarray(tr.predict(), np.float64)
    err = np.max(np.abs(p1 - p0)) / max(1.0, np.max(np.abs(p0)))
    assert err < 1e-5


def test_injected_data_without_flat8_tables_fails_fast():
    """Resolved attn_flat8 + injected data lacking the tables must be
    a construction-time ValueError, not a mid-trace IndexError."""
    from roc_tpu.models.gat import build_gat
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=11)
    pg = partition_graph(ds.graph, 4, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(4)
    ell_data = mh.shard_dataset_local(ds, pg, mesh, aggr_impl="ell")
    cfg = TrainConfig(verbose=False, aggr_impl="attn_flat8",
                      dropout_rate=0.0)
    with pytest.raises(ValueError, match="flat8"):
        DistributedTrainer(build_gat([12, 8, 3], dropout_rate=0.0),
                           ds, 4, cfg, mesh=mesh, data=ell_data, pg=pg)


def test_injected_sectioned_data_with_bdense_impl_fails_fast():
    """Sectioned-built data passes the sect_idx/sect_meta checks but
    carries no block plan; resolved aggr_impl='bdense' must raise at
    construction, not silently run the pure sectioned residual."""
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds = synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=11)
    pg = partition_graph(ds.graph, 4, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(4)
    sect_data = mh.shard_dataset_local(ds, pg, mesh,
                                       aggr_impl="sectioned")
    cfg = TrainConfig(verbose=False, aggr_impl="bdense",
                      dropout_rate=0.0)
    with pytest.raises(ValueError, match="block-dense"):
        DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                           ds, 4, cfg, mesh=mesh, data=sect_data,
                           pg=pg)

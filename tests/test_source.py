"""Partition-local loading tests: row-sliced loaders, FileSource, and
the spy asserting a host touches only its partitions' byte ranges
(VERDICT r1 #3; reference contract load_task.cu:41-51,201-245)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from roc_tpu.core import graph as G
from roc_tpu.core.graph import (Dataset, load_features, load_labels,
                                load_lux_rows, load_mask, save_dataset,
                                synthetic_dataset)
from roc_tpu.core.partition import (partition_col, partition_graph,
                                    partition_plan)
from roc_tpu.core.source import ArraySource, FileSource, as_source


@pytest.fixture(scope="module")
def disk_ds(tmp_path_factory):
    ds = synthetic_dataset(96, 6, in_dim=10, num_classes=3, seed=7)
    prefix = str(tmp_path_factory.mktemp("data") / "synth")
    save_dataset(ds, prefix, csv=True, feats_bin=False)
    return ds, prefix


def test_load_lux_rows_slices(disk_ds):
    ds, prefix = disk_ds
    g = ds.graph
    for lo, hi in [(0, 10), (5, 40), (90, 96), (0, 96), (7, 7)]:
        ptr, col = load_lux_rows(prefix + ".add_self_edge.lux", lo, hi)
        want_ptr = (g.row_ptr[lo:hi + 1] - g.row_ptr[lo])
        np.testing.assert_array_equal(ptr, want_ptr)
        np.testing.assert_array_equal(
            col, g.col_idx[g.row_ptr[lo]:g.row_ptr[hi]])


def test_row_sliced_loaders_match_full(disk_ds):
    ds, prefix = disk_ds
    V, F = ds.graph.num_nodes, ds.in_dim
    for lo, hi in [(0, 17), (31, 64), (64, 96)]:
        np.testing.assert_allclose(
            load_features(prefix, V, F, rows=(lo, hi)),
            ds.features[lo:hi], rtol=1e-5)
        np.testing.assert_array_equal(
            load_labels(prefix, V, ds.num_classes, rows=(lo, hi)),
            ds.labels[lo:hi])
        np.testing.assert_array_equal(
            load_mask(prefix, V, rows=(lo, hi)), ds.mask[lo:hi])


def test_feats_bin_rows_slice(tmp_path):
    ds = synthetic_dataset(40, 4, in_dim=6, num_classes=2, seed=1)
    prefix = str(tmp_path / "binonly")
    save_dataset(ds, prefix, csv=False, feats_bin=True)
    got = load_features(prefix, 40, 6, rows=(13, 29))
    np.testing.assert_allclose(got, ds.features[13:29], rtol=1e-6)


def test_file_source_matches_array_source(disk_ds):
    ds, prefix = disk_ds
    fs = FileSource(prefix, ds.in_dim, ds.num_classes)
    ars = as_source(ds)
    assert fs.num_nodes == ars.num_nodes
    assert fs.num_edges == ars.num_edges
    np.testing.assert_array_equal(fs.row_ptr(), ds.graph.row_ptr)
    np.testing.assert_array_equal(fs.col_slice(5, 50),
                                  ds.graph.col_idx[5:50])
    np.testing.assert_allclose(fs.features(10, 30), ds.features[10:30],
                               rtol=1e-5)
    np.testing.assert_array_equal(fs.labels(0, 96), ds.labels)
    np.testing.assert_array_equal(fs.mask(50, 96), ds.mask[50:])


def test_partition_local_reads_touch_only_local_rows(disk_ds,
                                                     monkeypatch):
    """The spy: partition p's column + feature reads must stay inside
    p's byte ranges (the O(V) row-pointer/offsets section is the one
    allowed global read)."""
    ds, prefix = disk_ds
    # use the binary feature cache so feature reads are seek-based
    save_dataset(ds, prefix, csv=False, feats_bin=True)
    fs = FileSource(prefix, ds.in_dim, ds.num_classes)
    plan = partition_plan(fs.row_ptr(), 4)
    reads = []
    real_read = G._read_slice

    def spy(f, offset, count, dtype):
        reads.append((f.name, offset, np.dtype(dtype).itemsize * count))
        return real_read(f, offset, count, dtype)

    monkeypatch.setattr(G, "_read_slice", spy)
    p = 1
    l, r = plan.bounds[p]
    e0, e1 = plan.edge_range(p)
    col = partition_col(plan, fs.col_slice, p)
    feats = fs.features(l, r + 1)
    col_base = 12 + plan.num_nodes * 8
    for name, off, nbytes in reads:
        if name.endswith(".lux"):
            lo_b, hi_b = col_base + e0 * 4, col_base + e1 * 4
        elif name.endswith(".feats.bin"):
            lo_b = l * ds.in_dim * 4
            hi_b = (r + 1) * ds.in_dim * 4
        else:
            raise AssertionError(f"unexpected read from {name}")
        assert lo_b <= off and off + nbytes <= hi_b, (
            f"{name}: read [{off}, {off+nbytes}) outside partition "
            f"range [{lo_b}, {hi_b})")
    assert len(reads) >= 2  # both the col slice and the feature slice
    # and the data is right
    np.testing.assert_array_equal(
        col[:e1 - e0], ds.graph.col_idx[e0:e1])
    np.testing.assert_allclose(feats, ds.features[l:r + 1], rtol=1e-6)


@pytest.mark.parametrize("aggr_impl", ["segment", "ell"])
def test_shard_dataset_local_matches_global(aggr_impl):
    """shard_dataset_local (per-part local builds) must produce the
    same device contents as the all-parts shard_dataset."""
    from roc_tpu.parallel import multihost as mh
    from roc_tpu.parallel.distributed import shard_dataset

    ds = synthetic_dataset(64, 6, in_dim=8, num_classes=3, seed=0)
    mesh = mh.make_parts_mesh(4)
    pg = partition_graph(ds.graph, 4, edge_multiple=64)
    want = shard_dataset(ds, pg, mesh, aggr_impl=aggr_impl)
    got = mh.shard_dataset_local(ds, pg, mesh, aggr_impl=aggr_impl)
    np.testing.assert_allclose(np.asarray(got.feats),
                               np.asarray(want.feats), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got.labels),
                                  np.asarray(want.labels))
    np.testing.assert_array_equal(np.asarray(got.mask),
                                  np.asarray(want.mask))
    # the layout's tables, whole: one tree, every array equal
    assert (jax.tree_util.tree_structure(got.tables)
            == jax.tree_util.tree_structure(want.tables))
    for a, b in zip(jax.tree_util.tree_leaves(got.tables),
                    jax.tree_util.tree_leaves(want.tables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_on_file_source_local_shards(disk_ds):
    """End to end: DistributedTrainer on shards built from FileSource
    row-sliced reads."""
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel import multihost as mh
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds, prefix = disk_ds
    fs = FileSource(prefix, ds.in_dim, ds.num_classes)
    mesh = mh.make_parts_mesh(4)
    cfg = TrainConfig(epochs=2, verbose=False, aggr_impl="ell",
                      symmetric=True)
    tr = DistributedTrainer(build_gcn([ds.in_dim, 8, 3]), ds, 4, cfg,
                            mesh=mesh)
    tr.data = mh.shard_dataset_local(fs, tr.pg, mesh, aggr_impl="ell")
    tr.train(epochs=2)
    assert np.isfinite(tr.evaluate()["train_loss"])


def test_shard_dataset_local_ring_matches_global():
    """Partition-local ring prep (pair lists from each process's own
    column reads + O(P) width agreement by collective) must produce
    byte-identical ring tables to the single-controller build over
    every part's stacked columns (``shard_dataset``)."""
    from roc_tpu.parallel import multihost as mh
    from roc_tpu.parallel.distributed import shard_dataset

    ds = synthetic_dataset(96, 7, in_dim=8, num_classes=3, seed=3)
    mesh = mh.make_parts_mesh(4)
    pg = partition_graph(ds.graph, 4, edge_multiple=64)
    want = shard_dataset(ds, pg, mesh, halo="ring")
    got = mh.shard_dataset_local(ds, pg, mesh, halo="ring")
    np.testing.assert_array_equal(np.asarray(got.tables.src),
                                  np.asarray(want.tables.src))
    np.testing.assert_array_equal(np.asarray(got.tables.dst),
                                  np.asarray(want.tables.dst))
    np.testing.assert_allclose(got.ring_padding_ratio,
                               want.ring_padding_ratio)
    np.testing.assert_allclose(np.asarray(got.feats),
                               np.asarray(want.feats), rtol=1e-6)


def test_ring_prep_reads_stay_partition_local(disk_ds, monkeypatch):
    """The ring prep's column reads must stay inside each partition's
    own .lux byte range — no host-side whole-graph pass (VERDICT r2
    weak #8)."""
    from roc_tpu.parallel import multihost as mh

    ds, prefix = disk_ds
    fs = FileSource(prefix, ds.in_dim, ds.num_classes)
    mesh = mh.make_parts_mesh(4)
    plan = partition_plan(fs.row_ptr(), 4)
    reads = []
    real_read = G._read_slice

    def spy(f, offset, count, dtype):
        reads.append((f.name, offset, np.dtype(dtype).itemsize * count))
        return real_read(f, offset, count, dtype)

    monkeypatch.setattr(G, "_read_slice", spy)
    mh.shard_dataset_local(fs, plan, mesh, halo="ring")
    col_base = 12 + plan.num_nodes * 8
    ranges = [tuple(col_base + e * 4 for e in plan.edge_range(p))
              for p in range(4)]
    lux_reads = [r for r in reads if r[0].endswith(".lux")]
    assert lux_reads, "expected column reads through the source"
    for name, off, nbytes in lux_reads:
        assert any(lo <= off and off + nbytes <= hi
                   for lo, hi in ranges), (
            f"column read [{off}, {off + nbytes}) spans beyond any "
            f"single partition's range {ranges}")


def test_trainer_ring_on_file_source_local_shards(disk_ds):
    """End to end: ring-halo DistributedTrainer on shards built from
    FileSource partition-local reads (previously NotImplementedError)."""
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel import multihost as mh
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    ds, prefix = disk_ds
    fs = FileSource(prefix, ds.in_dim, ds.num_classes)
    mesh = mh.make_parts_mesh(4)
    cfg = TrainConfig(epochs=2, verbose=False, halo="ring",
                      symmetric=True)
    tr = DistributedTrainer(build_gcn([ds.in_dim, 8, 3]), ds, 4, cfg,
                            mesh=mesh)
    tr.data = mh.shard_dataset_local(fs, tr.pg, mesh, halo="ring")
    tr.train(epochs=2)
    assert np.isfinite(tr.evaluate()["train_loss"])


def test_shard_dataset_local_sectioned_matches_global():
    """Partition-local sectioned prep (per-part counts + O(P*n_sec)
    max collective for the uniform chunk plan) must produce the same
    tables as the global build."""
    from roc_tpu.parallel import multihost as mh
    from roc_tpu.parallel.distributed import shard_dataset

    ds = synthetic_dataset(96, 7, in_dim=8, num_classes=3, seed=6)
    mesh = mh.make_parts_mesh(4)
    pg = partition_graph(ds.graph, 4, edge_multiple=64)
    want = shard_dataset(ds, pg, mesh, aggr_impl="sectioned")
    got = mh.shard_dataset_local(ds, pg, mesh, aggr_impl="sectioned")
    assert got.tables.meta == want.tables.meta
    assert len(got.tables.idx) == len(want.tables.idx)
    for a, b in zip(got.tables.idx, want.tables.idx):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(got.tables.sub_dst, want.tables.sub_dst):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""The generator: symmetric, every self edge, the target E, one
topology per graph_seed, features only from --seed."""

import numpy as np
import pytest

from substrates import skewed_homophilous as sub

V, E, C, F = 2048, 24576, 7, 32


@pytest.fixture(scope="module")
def topo():
    return sub.make_topology(V, E, C, graph_seed=5)


def edges(t):
    dst = np.repeat(np.arange(V), np.diff(t["row_ptr"]))
    return dst.astype(np.int64), t["col_idx"].astype(np.int64)


def test_csr_is_well_formed(topo):
    rp, ci = topo["row_ptr"], topo["col_idx"]
    assert rp.shape == (V + 1,) and rp[0] == 0 and rp[-1] == ci.shape[0]
    assert (np.diff(rp) >= 1).all()
    assert ci.min() >= 0 and ci.max() < V
    assert topo["labels"].shape == (V,)
    assert topo["labels"].min() >= 0 and topo["labels"].max() < C


def test_symmetric_with_every_self_edge_and_no_duplicates(topo):
    dst, src = edges(topo)
    fwd, bwd = np.sort(dst * V + src), np.sort(src * V + dst)
    assert np.array_equal(fwd, bwd)
    assert np.unique(fwd).shape == fwd.shape
    assert np.array_equal(np.unique(dst[dst == src]), np.arange(V))


@pytest.mark.parametrize("nodes,target", [(V, E), (4096, 200_000),
                                          (30_000, 450_000)])
def test_edge_count_within_one_percent(nodes, target):
    t = sub.make_topology(nodes, target, C, graph_seed=3)
    assert abs(t["col_idx"].shape[0] - target) <= 0.01 * target


def test_degree_is_skewed_and_edges_homophilous(topo):
    deg = np.diff(topo["row_ptr"])
    assert deg.max() > 8 * np.median(deg)
    dst, src = edges(topo)
    off = dst != src
    same = (topo["labels"][dst[off]] == topo["labels"][src[off]]).mean()
    assert 0.7 < same < 0.95     # 0.8 + the 1/C chance matches


def test_one_graph_seed_one_topology(topo):
    again = sub.make_topology(V, E, C, graph_seed=5)
    for k in ("row_ptr", "col_idx", "labels"):
        assert np.array_equal(topo[k], again[k])
    other = sub.make_topology(V, E, C, graph_seed=6)
    assert not np.array_equal(topo["col_idx"], other["col_idx"])


def test_seed_changes_features_and_mask_only(topo):
    a = sub.make_features(topo["labels"], F, C, seed=1)
    b = sub.make_features(topo["labels"], F, C, seed=1)
    c = sub.make_features(topo["labels"], F, C, seed=2)
    assert np.array_equal(a["features"], b["features"])
    assert np.array_equal(a["mask"], b["mask"])
    assert not np.array_equal(a["features"], c["features"])
    assert not np.array_equal(a["mask"], c["mask"])
    assert a["features"].dtype == np.float32
    assert a["features"].shape == (V, F)
    assert set(np.unique(a["mask"])) == {1, 2, 3}


def test_impossible_shapes_are_refused():
    with pytest.raises(ValueError):
        sub.make_topology(100, 50, C, graph_seed=1)
    with pytest.raises(ValueError):
        sub.make_topology(10, 1000, C, graph_seed=1)

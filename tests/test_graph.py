"""Graph container, .lux round-trip, self edges, partitioner."""

import numpy as np
import pytest

from roc_tpu.core.graph import (Graph, add_self_edges, check_symmetric,
                                from_edge_list, load_lux, save_lux,
                                synthetic_dataset, synthetic_graph)
from roc_tpu.core.partition import (edge_balanced_bounds, padded_edge_list,
                                    partition_graph)


def tiny_graph():
    # 0->1 means edge dst=1 src=0 in our dst-major CSR
    src = [0, 1, 2, 3, 0]
    dst = [1, 2, 3, 0, 2]
    return from_edge_list(src, dst, 4, symmetrize=True)


def test_from_edge_list_csr():
    g = tiny_graph()
    assert g.num_nodes == 4
    assert check_symmetric(g)
    # row of dst=1 must contain src 0
    row1 = g.col_idx[g.row_ptr[1]:g.row_ptr[2]]
    assert 0 in row1


def test_add_self_edges():
    g = add_self_edges(tiny_graph())
    assert g.has_all_self_edges()
    deg = g.in_degree
    assert (deg >= 1).all()
    # idempotent
    g2 = add_self_edges(g)
    assert g2.num_edges == g.num_edges


def test_lux_roundtrip(tmp_path):
    g = add_self_edges(synthetic_graph(50, 4, seed=3))
    path = str(tmp_path / "g.lux")
    save_lux(g, path)
    g2 = load_lux(path)
    np.testing.assert_array_equal(g.row_ptr, g2.row_ptr)
    np.testing.assert_array_equal(g.col_idx, g2.col_idx)


def test_transpose_symmetric_identity():
    g = add_self_edges(synthetic_graph(30, 5, seed=1))
    t = g.transpose()
    assert check_symmetric(g)
    assert t.num_edges == g.num_edges
    # symmetric graph: transpose has identical row degrees
    np.testing.assert_array_equal(g.in_degree, t.in_degree)


def test_edge_balanced_bounds_cover_all_vertices():
    g = synthetic_graph(100, 6, seed=0, power_law=True)
    for P in (1, 2, 4, 8):
        bounds = edge_balanced_bounds(g.row_ptr, P)
        assert len(bounds) == P
        covered = []
        for (l, r) in bounds:
            if r >= l:
                covered.extend(range(l, r + 1))
        assert covered == list(range(g.num_nodes))


def test_edge_balance_quality():
    g = synthetic_graph(1000, 16, seed=0)
    P = 8
    bounds = edge_balanced_bounds(g.row_ptr, P)
    edges = [int(g.row_ptr[r + 1] - g.row_ptr[l]) if r >= l else 0
             for (l, r) in bounds]
    cap = (g.num_edges + P - 1) // P
    # greedy closes a range only after exceeding cap; each range holds at
    # most cap + max_degree edges
    max_deg = int(g.in_degree.max())
    assert max(edges) <= cap + max_deg + 1


def test_partition_graph_shapes_and_content():
    g = add_self_edges(synthetic_graph(100, 6, seed=2))
    P = 4
    pg = partition_graph(g, P, node_multiple=8, edge_multiple=32)
    assert pg.part_row_ptr.shape == (P, pg.part_nodes + 1)
    assert pg.part_col_idx.shape == (P, pg.part_edges)
    assert (pg.part_row_ptr[:, -1] == pg.part_edges).all()
    # real edges reproduce the global CSR
    for p in range(P):
        l, r = pg.bounds[p]
        if r < l:
            continue
        e = int(pg.real_edges[p])
        got = pg.part_col_idx[p, :e]
        want = g.col_idx[g.row_ptr[l]:g.row_ptr[r + 1]]
        np.testing.assert_array_equal(got, want)
        # degrees match
        np.testing.assert_array_equal(
            pg.part_in_degree[p, :int(pg.real_nodes[p])],
            g.in_degree[l:r + 1])
    # padding edges all point at the dummy source
    for p in range(P):
        e = int(pg.real_edges[p])
        assert (pg.part_col_idx[p, e:] == pg.dummy_src).all()


def test_partition_chunk_span_invariant():
    """A run of C consecutive local edges must span <= C local rows
    (destinations sorted, every row with a self edge)."""
    g = add_self_edges(synthetic_graph(200, 5, seed=4, power_law=True))
    for P in (1, 3, 8):
        pg = partition_graph(g, P, node_multiple=8, edge_multiple=64)
        for p in range(P):
            ptr = pg.part_row_ptr[p]
            dst = np.repeat(np.arange(pg.part_nodes), np.diff(ptr))
            assert dst.shape[0] == pg.part_edges
            C = 64
            for c0 in range(0, pg.part_edges, C):
                span = dst[c0:c0 + C]
                assert span[-1] - span[0] < C


def test_global_pad_map():
    g = add_self_edges(synthetic_graph(50, 4, seed=5))
    pg = partition_graph(g, 4, node_multiple=8)
    m = pg.global_pad_map()
    assert m.shape == (pg.padded_num_nodes,)
    real = m[m < g.num_nodes]
    np.testing.assert_array_equal(np.sort(real), np.arange(g.num_nodes))


def test_padded_edge_list():
    g = add_self_edges(synthetic_graph(33, 3, seed=6))
    src, dst = padded_edge_list(g, multiple=64)
    assert src.shape[0] % 64 == 0
    E = g.num_edges
    np.testing.assert_array_equal(src[:E], g.col_idx)
    assert (src[E:] == g.num_nodes).all()
    assert (dst[E:] == g.num_nodes - 1).all()
    assert (np.diff(dst) >= 0).all()


def test_synthetic_dataset_deterministic():
    d1 = synthetic_dataset(64, 6, seed=7)
    d2 = synthetic_dataset(64, 6, seed=7)
    np.testing.assert_array_equal(d1.features, d2.features)
    np.testing.assert_array_equal(d1.graph.col_idx, d2.graph.col_idx)
    assert d1.graph.has_all_self_edges()

"""A layout's tables as one value (``models/tables.py``): one builder for
one device, for stacked parts and for the parts one process holds.

Held here: the per-partition view of the stacked tables has the
one-device tables' tree, fused or not; the single-controller and the
multi-host builds of a P=2 split (the multi-host build bakes no fused
weights) are equal leaf for leaf; a typed graph's scanned relation
tables sum what its edge lists sum; and the distributed steps'
programs, which take the tables as one argument, lower to the text
pinned below.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.core.partition import partition_graph
from roc_tpu.core.relations import derive_typed
from roc_tpu.models.tables import (BlockDenseTables, EdgeList, EllTables,
                                   FlatTables, RelationTables,
                                   SectionedTables)
from roc_tpu.models.gcn import build_gcn
from roc_tpu.parallel import multihost as mh
from roc_tpu.parallel.distributed import DistributedTrainer, shard_dataset
from roc_tpu.train.trainer import TrainConfig, make_graph_context

LAYOUTS = {"segment": EdgeList, "sectioned": SectionedTables,
           "flat_sum": FlatTables, "ell": EllTables,
           "bdense": BlockDenseTables, "attn_flat8": FlatTables}
# four of the nine [128, 128] tiles qualify: tiles and residual both
KNOBS = {"bdense": {"bdense_min_fill": 120}}


@pytest.fixture(scope="module")
def ds():
    # 300 vertices over two parts with padding rows: no part is full,
    # so a part's padding edges sit on padding rows and every row keeps
    # its degree (and its ELL bucket) in both forms
    return synthetic_dataset(300, 6, in_dim=8, num_classes=3, seed=5)


def _tree(tables):
    """A value's tree without its static aux: its type, and every
    array's path, rank and dtype."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tables)
    return type(tables), [(jax.tree_util.keystr(k), np.ndim(a),
                           np.dtype(a.dtype)) for k, a in leaves]


@pytest.mark.parametrize("fuse", [False, True], ids=["plain", "fused"])
@pytest.mark.parametrize("layout", list(LAYOUTS) + ["relations"])
def test_stacked_builds_agree_and_slice_to_the_one_device_tree(
        ds, layout, fuse):
    if layout == "relations":
        # a typed graph's tables are built for one device alone; its
        # passes carry their own 1 / deg_r weights, fused or not.  The
        # scanned tables (flat width-8, slot-major) and the edge lists
        # are two derivations of each forward pass: they sum alike
        typed = synthetic_dataset(100, 6, in_dim=8, num_classes=3,
                                  seed=5)
        typed.typed = derive_typed(typed.graph, (60, 40))
        got = {impl: make_graph_context(
            typed, impl, fuse=fuse,
            rel_orders=("transform_first", "gather_first")).tables
            for impl in ("flat_sum", "segment")}
        for impl, t in got.items():
            assert isinstance(t, RelationTables)
            assert t.edge_list == (impl == "segment")
        rng = np.random.RandomState(0)
        for name, _, out_rows, *_ in got["segment"].meta:
            x = jnp.asarray(rng.randn(out_rows, 16).astype(np.float32))
            np.testing.assert_allclose(
                np.asarray(got["flat_sum"].apply(x, name, None)),
                np.asarray(got["segment"].apply(x, name, None)),
                rtol=1e-5, atol=1e-6)
        return
    kw = KNOBS.get(layout, {})
    one = make_graph_context(ds, layout, fuse=fuse, **kw).tables
    assert isinstance(one, LAYOUTS[layout])
    if layout == "bdense":
        assert one.a is not None and one.residual.idx
    pg = partition_graph(ds.graph, 2, node_multiple=8, edge_multiple=64)
    mesh = mh.make_parts_mesh(2)
    glo = shard_dataset(ds, pg, mesh, aggr_impl=layout, aggr_fuse=fuse,
                        **kw).tables
    plain = glo if not fuse else shard_dataset(
        ds, pg, mesh, aggr_impl=layout, **kw).tables
    loc = mh.shard_dataset_local(ds, pg, mesh, aggr_impl=layout,
                                 **kw).tables
    # the two stacked builds: one tree (static aux included), every
    # array equal
    assert (jax.tree_util.tree_structure(loc)
            == jax.tree_util.tree_structure(plain))
    for a, b in zip(jax.tree_util.tree_leaves(loc),
                    jax.tree_util.tree_leaves(plain)):
        assert a.shape[0] == 2
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a partition's view is the one-device tables' tree
    part = jax.tree_util.tree_map(lambda a: a[0], glo)
    assert _tree(part) == _tree(one)
    assert one.weighted == (fuse and layout not in ("segment",
                                                    "attn_flat8"))


# The SHA-256 of the distributed train and eval programs at P=2 below,
# as the commit before the tables became one value lowered them (its
# steps took them as eighteen positional arguments): the programs are
# left token for token.
DIST_PROGRAMS = {
    "flat_sum": (
        "8fda44b4573644260d5875ac159d144025ab5e269e16bbc0a2065ea96a58d075",
        "e83e17acfbbd675226112faacb768219b4b01a5a72fffc2ea7a1a3521249c92b"),
    "sectioned": (
        "d55606cbd1837264f93a8f626f26daaf03472cbc19089bcf55ab6ac5677576e0",
        "4887d87e8fa2bbf74b130a65d3b79ab13ca8346fab3977365c3f26e83c2e7031"),
}


@pytest.mark.parametrize("layout", list(DIST_PROGRAMS))
def test_distributed_programs_are_pinned(layout):
    ds = synthetic_dataset(100, 6, in_dim=8, num_classes=3, seed=5)
    tr = DistributedTrainer(
        build_gcn([8, 16, 3]), ds, 2, TrainConfig(
            verbose=False, aggr_impl=layout, epochs=1,
            eval_every=1 << 30, dtype=jnp.float32,
            compute_dtype=jnp.bfloat16))
    lr = jnp.float32(0.01)
    train = tr._train_step._jit.lower(
        *tr._train_args(tr.key, lr)).as_text()
    evl = tr._eval_step._jit.lower(*tr._eval_args()).as_text()
    assert tuple(hashlib.sha256(t.encode()).hexdigest()
                 for t in (train, evl)) == DIST_PROGRAMS[layout]

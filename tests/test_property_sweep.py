"""Cross-implementation equivalence sweep over randomized graphs.

The aggregation layouts (ell / sectioned / flat_sum / bdense incl.
grouped+u4-packed) must agree with the segment reference on ANY graph
— including the structures that historically broke layouts:
zero-degree rows, hub rows (bucket width >> mean), single-node
components, and empty-ish partitions.  The fixed fixtures elsewhere
pin one shape each; this sweep randomizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import Dataset, Graph, from_edge_list
from roc_tpu.models.gcn import build_gcn
from roc_tpu.train.trainer import TrainConfig, Trainer, make_graph_context

IMPLS = ("segment", "ell", "sectioned", "flat_sum")
# block-dense variants: min_fill=1 forces tiles on any graph, the
# planted hub's duplicate edges exercise uint8/u4 multiplicity
# saturation and the packing fallback, group=4 the padded-run reduction
BDENSE = {"bdense": {}, "bdense_g4": {"bdense_group": 4}}


def _random_stress_graph(seed: int) -> Graph:
    """Graphs with planted pathologies: hubs, isolated rows, skew."""
    rng = np.random.RandomState(seed)
    V = int(rng.randint(40, 200))
    E = int(rng.randint(V, V * 12))
    src = rng.randint(0, V, size=E)
    dst = rng.randint(0, V, size=E)
    # plant a hub: one destination absorbs 25% of edges
    hub = int(rng.randint(V))
    dst[: E // 4] = hub
    # plant isolated rows by construction: never target the last rows
    iso = max(1, V // 10)
    dst = np.where(dst >= V - iso, (dst - iso) % max(V - iso, 1), dst)
    return from_edge_list(src, dst, V)


@pytest.mark.parametrize("layout", IMPLS[1:] + tuple(BDENSE))
@pytest.mark.parametrize("seed", range(6))
def test_aggregation_impls_agree_on_stress_graphs(seed, layout):
    g = _random_stress_graph(seed)
    rng = np.random.RandomState(seed + 100)
    ds = Dataset(graph=g,
                 features=rng.randn(g.num_nodes, 16).astype(np.float32),
                 labels=rng.randint(0, 3, g.num_nodes).astype(np.int32),
                 mask=np.ones(g.num_nodes, np.int32), num_classes=3)
    feats = jnp.asarray(ds.features)
    model = build_gcn([16, 8, 3], dropout_rate=0.0)
    params = model.init_params(jax.random.PRNGKey(seed))

    def forward(aggr_impl, **kw):
        gctx = make_graph_context(ds, aggr_impl=aggr_impl, chunk=64,
                                  **kw)
        return gctx, np.asarray(
            model.apply(params, feats, gctx, train=False))

    if layout in BDENSE:
        gctx, got = forward("bdense", bdense_min_fill=1,
                            **BDENSE[layout])
        assert gctx.bd_a is not None
    else:
        _, got = forward(layout)
    np.testing.assert_allclose(got, forward("segment")[1], rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("seed", range(3))
def test_distributed_matches_single_on_stress_graphs(seed):
    """4-part SPMD loss == single-device loss on the same stress
    graph with identical params (partition-count invariance under
    hubs/isolated rows)."""
    from roc_tpu.parallel.distributed import DistributedTrainer
    g = _random_stress_graph(seed + 50)
    rng = np.random.RandomState(seed)
    ds = Dataset(graph=g,
                 features=rng.randn(g.num_nodes, 12).astype(np.float32),
                 labels=rng.randint(0, 3, g.num_nodes).astype(np.int32),
                 mask=rng.choice([1, 2, 3], g.num_nodes).astype(np.int32),
                 num_classes=3)
    model = build_gcn([12, 8, 3], dropout_rate=0.0)
    cfg = TrainConfig(aggr_impl="ell", verbose=False, chunk=64,
                      eval_every=1 << 30, symmetric=None)
    dt = DistributedTrainer(model, ds, 4, cfg)
    tr = Trainer(model, ds, cfg)
    tr.params = jax.device_get(dt.params)
    md, ms = dt.evaluate(), tr.evaluate()
    assert md["train_loss"] == pytest.approx(ms["train_loss"],
                                             rel=1e-4)
    assert md["test_correct"] == ms["test_correct"]

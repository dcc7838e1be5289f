"""The plain reference against the program's ``segment`` path on the
fixture graph: float32 agrees to summation order, bfloat16 fails a
float32 tolerance."""

import json
import os

import numpy as np
import pytest

from conftest import FIXTURES, ROOT

import reference
from substrates import skewed_homophilous as sub

V, E = 2048, 24576


def load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


TOL = load("workloads/tiny-gcn.fullgraph.json")["correct"]


@pytest.fixture(scope="module")
def data():
    t = sub.make_topology(V, E, 7, graph_seed=5)
    f = sub.make_features(t["labels"], 32, 7, seed=1)
    return {**t, **f}


def system_logits(cfg, data, dtype):
    """The program's own forward through its plain ``segment``
    aggregation, in inference mode."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from roc_tpu.core.graph import Dataset, Graph
    from roc_tpu.models import model_builders
    from roc_tpu.train.trainer import make_graph_context
    layers = cfg["model"]["layers"]
    model = model_builders()[cfg["model"]["family"]](layers,
                                                     dropout_rate=0.5)
    ds = Dataset(Graph(data["row_ptr"], data["col_idx"]),
                 data["features"], data["labels"], data["mask"],
                 num_classes=layers[-1])
    gctx = make_graph_context(ds, "segment")
    params = model.init_params(jax.random.PRNGKey(3))
    cast = {k: v.astype(dtype) for k, v in params.items()}
    logits = model.apply(cast, jnp.asarray(data["features"], dtype), gctx,
                         key=None, train=False)
    return params, np.asarray(logits, dtype=np.float32)


@pytest.mark.parametrize("config,ref", [
    ("tiny-gcn", "gcn"), ("tiny-gcn-deep", "gcn"), ("tiny-sage", "sage")])
def test_float32_agrees_and_bfloat16_fails(data, config, ref):
    import importlib
    import jax.numpy as jnp
    cfg = load(f"configs/{config}.json")
    forward = importlib.import_module(f"references.{ref}").forward

    def check(dtype):
        params, logits = system_logits(cfg, data, dtype)
        got = reference.run(forward, params, data["features"],
                            data["labels"], data["mask"], data["row_ptr"],
                            data["col_idx"], cfg["model"])
        return reference.compare(logits, got["logits"])

    fine = check(jnp.float32)
    assert fine["row_rel_l2_max"] <= TOL["row_rel_l2_max"], fine
    assert fine["row_rel_l2_median"] <= TOL["row_rel_l2_median"], fine
    coarse = check(jnp.bfloat16)
    assert coarse["row_rel_l2_median"] > TOL["row_rel_l2_median"], coarse
    assert coarse["row_rel_l2_max"] > TOL["row_rel_l2_max"], coarse


def test_aggregate_sum_matches_a_dense_adjacency(data):
    import jax.numpy as jnp
    n = 256
    t = sub.make_topology(n, 3000, 4, graph_seed=1)
    dst = np.repeat(np.arange(n), np.diff(t["row_ptr"]))
    a = np.zeros((n, n), np.float32)
    a[dst, t["col_idx"]] = 1.0
    x = np.random.default_rng(0).standard_normal((n, 5)).astype(np.float32)
    g = reference.Graph.from_csr(t["row_ptr"], t["col_idx"], widest=5)
    assert g.src.shape[0] == 0 and g.tail_src.shape[0] > 0
    g = reference.Graph(*(jnp.asarray(a) for a in g.arrays()), n)
    got = np.asarray(reference.aggregate_sum(jnp.asarray(x), g))
    np.testing.assert_allclose(got, a @ x, rtol=1e-5, atol=1e-5)
    # the same through whole chunks plus a tail
    src, dst = np.asarray(g.tail_src), np.asarray(g.tail_dst)
    whole = (src.shape[0] // 512) * 512
    chunked = reference.Graph(
        jnp.asarray(src[:whole].reshape(-1, 512)),
        jnp.asarray(dst[:whole].reshape(-1, 512)),
        jnp.asarray(src[whole:]), jnp.asarray(dst[whole:]), g.degree, n)
    assert chunked.src.shape[0] >= 2 and chunked.tail_src.shape[0] > 0
    again = np.asarray(reference.aggregate_sum(jnp.asarray(x), chunked))
    np.testing.assert_allclose(again, a @ x, rtol=1e-5, atol=1e-5)


def test_compare_reports_a_wrong_row():
    r = np.random.default_rng(0).standard_normal((100, 7))
    s = r.copy()
    s[17] *= 1.5
    out = reference.compare(s, r)
    assert out["row_rel_l2_median"] == 0.0
    assert abs(out["row_rel_l2_max"] - 0.5) < 1e-9
    with pytest.raises(ValueError):
        reference.compare(s[:50], r)

"""(parts, model) 2-D mesh training parity (ISSUE 16): the tentpole's
end-to-end guarantee.  Training on EVERY (parts, model) factorization
of the 8-virtual-device rig produces the same learning trajectory as
today's 1-D all-parts mesh at the same partition count — fwd + grad +
update within 1e-5 after multiple epochs — including the fused
flat_sum aggregate and the ring halo schedule, with parameters
model-SHARDED at rest whenever model > 1 (the replication-ledger
ratchet's live counterpart; the modeled side is tests/
test_sharding_lint.py)."""

import numpy as np
import pytest

import jax

from roc_tpu.core.graph import MASK_NONE, Dataset, random_csr
from roc_tpu.models.gcn import build_gcn
from roc_tpu.parallel import (MODEL_AXIS, candidate_mesh_shapes,
                              model_shard_spec)
from roc_tpu.parallel.distributed import DistributedTrainer
from roc_tpu.train.trainer import TrainConfig

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-virtual-device rig")

V, F, C = 192, 48, 6


@pytest.fixture(scope="module")
def dataset():
    g = random_csr(V, 6 * V, seed=0)
    rng = np.random.RandomState(1)
    ds = Dataset(graph=g, features=rng.rand(V, F).astype(np.float32),
                 labels=rng.randint(0, C, size=V).astype(np.int32),
                 mask=np.full(V, MASK_NONE, dtype=np.int32),
                 num_classes=C, name="mesh2d")
    ds.mask[rng.rand(V) < 0.5] = 1
    return ds


def _train(ds, parts, mesh, epochs=3, **kw):
    cfg = TrainConfig(verbose=False, symmetric=True, dropout_rate=0.0,
                      eval_every=1 << 30, mesh=mesh, **kw)
    tr = DistributedTrainer(build_gcn([F, 24, C], dropout_rate=0.0),
                            ds, parts, cfg)
    tr.train(epochs=epochs)
    tr.sync()
    return tr


def _assert_parity(ref, got, tol=1e-5):
    """Identical trajectory: every parameter leaf within tol after the
    full fwd+grad+update loop, and the evaluated loss agrees."""
    pr = jax.device_get(ref.params)
    pg = jax.device_get(got.params)
    assert sorted(pr) == sorted(pg)
    for k in pr:
        d = float(np.max(np.abs(np.asarray(pr[k], np.float64)
                                - np.asarray(pg[k], np.float64))))
        assert d <= tol, (k, d)
    assert got.evaluate()["train_loss"] == pytest.approx(
        ref.evaluate()["train_loss"], abs=1e-5)


def _assert_model_sharded_at_rest(tr, model):
    """Params AND Adam moments whose shape carries a model-divisible
    dim actually live split over MODEL_AXIS (not just modeled so)."""
    sharded = 0
    for tree in (tr.params, tr.opt_state.m, tr.opt_state.v):
        for k, leaf in tree.items():
            spec = model_shard_spec(np.shape(leaf), model)
            if spec is None:
                continue
            sharded += 1
            assert tuple(leaf.sharding.spec) == spec, \
                (k, leaf.sharding.spec, spec)
    assert sharded > 0, "no leaf left the replicated layout"


@pytest.mark.parametrize(
    "shape", candidate_mesh_shapes(8),
    ids=lambda s: f"{s[0]}x{s[1]}")
def test_training_parity_every_mesh_shape(dataset, shape):
    """1-D vs 2-D parity on every factorization of the rig, reference
    rebuilt at the SAME partition count (the parts axis is the
    partition count; only the model axis is new)."""
    parts, model = shape
    ref = _train(dataset, parts, "auto")
    two = _train(dataset, parts, f"{parts}x{model}")
    if model > 1:
        _assert_model_sharded_at_rest(two, model)
    _assert_parity(ref, two)


def test_training_parity_flat_sum_fused_aggregate(dataset):
    """The fused aggregate keeps parity on the 2-D mesh (the flat8
    scan runs inside the partial-auto shard_map body)."""
    ref = _train(dataset, 2, "auto", aggr_impl="flat_sum")
    two = _train(dataset, 2, "2x4", aggr_impl="flat_sum")
    _assert_model_sharded_at_rest(two, 4)
    _assert_parity(ref, two)


def test_training_parity_ring_halo(dataset):
    """halo='ring' on the 2-D mesh runs the step fully manual over
    both axes (ppermute cannot cross a partial-auto boundary) — the
    trajectory still matches, and params still rest model-sharded
    between steps."""
    ref = _train(dataset, 2, "auto", halo="ring")
    two = _train(dataset, 2, "2x4", halo="ring")
    _assert_model_sharded_at_rest(two, 4)
    _assert_parity(ref, two)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_psum_halo_gather_matches_all_gather(dtype):
    """The partial-auto halo gather (psum of placed blocks; 16-bit
    floats as uint16 bit patterns) equals lax.all_gather in value AND
    in its vjp — the bitcast has no derivative of its own, and a bf16
    all-reduce under a partial-auto axis aborts XLA:CPU outright."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from roc_tpu.parallel import PARTS_AXIS
    from roc_tpu.parallel.distributed import _gather_by_psum, _shard_map
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                (PARTS_AXIS, MODEL_AXIS))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 8), dtype)
    ct = jnp.asarray(np.random.RandomState(1).randn(32, 8), dtype)
    pids = jnp.arange(2, dtype=jnp.int32)

    def run(gather, axis_names):
        def body(x, ct, pids):
            full, vjp = jax.vjp(lambda b: gather(b, pids[0]), x[0])
            return full, vjp(ct)[0][None]
        f = _shard_map(body, mesh,
                       (P(PARTS_AXIS), P(), P(PARTS_AXIS)),
                       (P(), P(PARTS_AXIS)), axis_names=axis_names)
        return [np.asarray(a, np.float32) for a in jax.jit(f)(x, ct, pids)]

    ref = run(lambda b, _: lax.all_gather(
        b, PARTS_AXIS, axis=0, tiled=True), frozenset())
    got = run(lambda b, pid: _gather_by_psum(b, pid, 2),
              frozenset({PARTS_AXIS}))
    np.testing.assert_array_equal(got[0], np.asarray(
        x, np.float32).reshape(32, 8))
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])

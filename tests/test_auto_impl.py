"""What ``--impl`` may name, and how ``auto`` reaches each layout:
the generation-keyed sectioned window and its calibration file
(core/ell.py), a route from :func:`resolve_auto_impl_probed` to every
choice the CLI offers besides ``auto`` and the ``segment`` reference,
and the refusal of a name that left the program — on the command line
or stored in an artifact."""

import dataclasses
import json
import os

import pytest

import jax

from roc_tpu.core.ell import AGGR_IMPLS

REMOVED = ("blocked", "scan", "pallas")
CHOICES = "{" + ",".join(AGGR_IMPLS) + "}"


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


# ---- every choice is the reference or reachable ----

class _Shape:
    """The two sizes the arithmetic resolution reads; a route that
    must not run the structure probe carries no arrays to probe."""

    def __init__(self, num_nodes, num_edges):
        self.num_nodes, self.num_edges = num_nodes, num_edges
        self.row_ptr = self.col_idx = None


# layout -> (V, E, out_rows, dense fraction the census reports or None
# where the probe must stay off), on the one device kind with a
# measured window.  Reddit (sectioned), a small graph (ell), products
# at four partitions (flat_sum), Reddit on a community order (bdense).
ROUTES = {
    "sectioned": (232_965, 114_848_857, None, 0.01),
    "ell": (50_000, 10_000_000, None, None),
    "flat_sum": (2_449_029, 126_167_309, 612_258, None),
    "bdense": (232_965, 114_848_857, None, 0.60),
}


def test_both_clis_take_their_impl_choices_from_core_ell(capsys):
    """Training and export accept each of the six and nothing else."""
    from roc_tpu.serve import export
    from roc_tpu.train import cli
    assert AGGR_IMPLS[:2] == ("auto", "segment")
    for parse, base in ((cli.parse_args, []),
                        (export.parse_args, ["--out", "x"])):
        for name in AGGR_IMPLS:
            assert parse(base + ["--impl", name]).impl == name
        with pytest.raises(SystemExit):
            parse(base + ["--impl", "attn_flat8"])
        assert CHOICES in capsys.readouterr().err


@pytest.mark.parametrize("impl", AGGR_IMPLS[2:])
def test_every_impl_choice_is_the_reference_or_reachable(
        impl, monkeypatch):
    """ROADMAP D2's end state: a value of ``--impl`` is ``auto``, the
    ``segment`` reference, or a layout some input makes ``auto``
    return.  A new choice without a row in ROUTES fails here."""
    from roc_tpu.ops import blockdense as bd
    from roc_tpu.train.trainer import resolve_auto_impl_probed
    V, E, out_rows, dense_frac = ROUTES[impl]
    monkeypatch.setenv("ROC_TPU_DEVICE_KIND", "TPU v5 lite")
    monkeypatch.delenv("ROC_TPU_CALIBRATION", raising=False)
    probed = []

    def census(row_ptr, col_idx, num_nodes, **kw):
        probed.append(num_nodes)
        return dense_frac, ("keys", "counts")

    monkeypatch.setattr(bd, "probe_dense_frac", census)
    got, cen = resolve_auto_impl_probed(_Shape(V, E), out_rows=out_rows)
    assert got == impl
    assert bool(probed) == (dense_frac is not None)
    assert (cen is not None) == (impl == "bdense")


# ---- a removed name is refused, wherever it comes from ----

@pytest.mark.parametrize("argv", [["--impl", name] for name in REMOVED]
                         + [["--allow-slow-impl"]],
                         ids=REMOVED + ("--allow-slow-impl",))
def test_cli_rejects_removed_impl(argv, capsys):
    from roc_tpu.train import cli
    with pytest.raises(SystemExit) as exc:
        cli.main(["--cpu", "-layers", "8-8-3"] + argv)
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert CHOICES in err and argv[-1] in err


def _export_with(tmp_path, name):
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.serve.export import (MANIFEST_NAME, MANIFEST_VERSION,
                                      load_predictor)
    (tmp_path / MANIFEST_NAME).write_text(json.dumps({
        "version": MANIFEST_VERSION,
        "model": build_gcn([8, 8, 3]).to_spec(),
        "config": {"aggr_impl": name}}))
    return lambda: load_predictor(str(tmp_path))


def _checkpoint_with(tmp_path, name):
    """A checkpoint as a build that still had ``name`` wrote it: the
    resolved config goes into the fingerprint's elastic half."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer
    from roc_tpu.utils.checkpoint import (checkpoint_trainer,
                                          restore_trainer)
    ds = synthetic_dataset(48, 4, in_dim=8, num_classes=3, seed=0)
    tr = Trainer(build_gcn([8, 8, 3]), ds,
                 TrainConfig(aggr_impl="segment", verbose=False))
    current = tr.config
    path = str(tmp_path / "ck")
    checkpoint_trainer(tr, path)
    restore_trainer(tr, path)               # a current name restores
    tr.config = dataclasses.replace(current, aggr_impl=name)
    checkpoint_trainer(tr, path)
    tr.config = current
    return lambda: restore_trainer(tr, path)


@pytest.mark.parametrize("where,name", [
    ("export", "blocked"), ("export", "pallas"),
    ("checkpoint", "blocked")])
def test_stored_removed_impl_is_refused(where, name, tmp_path):
    """Not a KeyError, not a silent ``segment``: the error names the
    stored value and the six that exist."""
    load = {"export": _export_with,
            "checkpoint": _checkpoint_with}[where](tmp_path, name)
    with pytest.raises(ValueError) as exc:
        load()
    msg = str(exc.value)
    assert repr(name) in msg
    assert all(impl in msg for impl in AGGR_IMPLS)

"""Quantized serving tables (PR 19, ``roc_tpu/serve/quant.py``):

- round-trip identity: ``quantize∘dequantize∘quantize`` is bit-exact
  (per-row symmetric scaling maps the row max to ±Q), the property
  cold-load requantization and incremental refresh exactness rest on;
- params codec: ≥2-D float leaves quantize per-row with ``::scale``
  companions, 1-D leaves pass verbatim, and the codec round-trips;
- export→cold-load→query parity at int8: the cold-loaded predictor
  serves the export-process predictor's gated values bit-exactly and
  stays within the drift gate vs the fp32 trainer reference;
- ZERO new compiles on an int8 cold start (the test_serve acceptance,
  re-proven for the quantized program set in a child process);
- ``add_edges`` requantize-exactness: refreshing only the recomputed
  rows equals quantizing a full rebuild, codes and scales bit-equal;
- mid-rollout ``publish_quant``: a batch pinned to the fp32 version
  keeps serving fp32 bit-exactly after int8 publishes (quant-spec-
  pinned), and swapping back restores the original values;
- refusal paths: export REFUSES (no files written) past the drift
  thresholds, and an invalidation whose refreshed rows blow the
  pinned scale envelope refuses with the old version still published;
- fp8: byte-view persistence round-trips the dtype through npz, and
  export works behind explicitly relaxed thresholds (fp8-e4m3's 3
  mantissa bits intentionally fail the default gate).
"""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "serve_worker.py")

_SERVE_ENTRY = re.compile(r"jit__serve_step")


def _dataset(V=300, seed=0):
    from roc_tpu.core.graph import synthetic_dataset
    return synthetic_dataset(num_nodes=V, avg_degree=6, in_dim=24,
                             num_classes=5, seed=seed)


def _sgc_model():
    from roc_tpu.models.sgc import build_sgc
    return build_sgc([24, 5], k=2, dropout_rate=0.5)


def _config(**kw):
    from roc_tpu.train.trainer import TrainConfig
    kw.setdefault("verbose", False)
    kw.setdefault("symmetric", True)
    return TrainConfig(**kw)


@pytest.fixture(scope="module")
def sgc_rig():
    from roc_tpu.train.trainer import Trainer
    ds = _dataset()
    tr = Trainer(_sgc_model(), ds, _config())
    tr.train(2)
    return ds, tr, np.asarray(jax.device_get(tr.predict()))


# ------------------------------------------------------------- codec

def test_roundtrip_identity_int8():
    """quantize∘dequantize∘quantize is bit-exact: the dequantized
    table requantizes to the SAME codes and scales (each row's max
    maps back to ±127 exactly), so a cold load reconstructs the
    device table bit-identically."""
    from roc_tpu.serve.quant import dequantize_rows, quantize_rows
    rng = np.random.RandomState(0)
    x = rng.randn(64, 24).astype(np.float32) * 5.0
    x[3] = 0.0                      # all-zero row: scale falls to 1.0
    q1, s1 = quantize_rows(x, "int8")
    assert q1.dtype == np.int8 and s1.dtype == np.float32
    assert float(s1[3]) == 1.0
    d = dequantize_rows(q1, s1)
    q2, s2 = quantize_rows(d, "int8")
    assert np.array_equal(q1, q2)
    assert np.array_equal(s1, s2)


def test_params_codec_roundtrip():
    """≥2-D float leaves quantize per-row with ``::scale``
    companions; 1-D leaves pass verbatim; decode inverts encode."""
    from roc_tpu.serve.quant import (PARAMS_SCALE_SUFFIX,
                                     dequantize_params,
                                     quantize_params)
    rng = np.random.RandomState(1)
    host = {"head/w": rng.randn(24, 5).astype(np.float32),
            "head/b": rng.randn(5).astype(np.float32)}
    store, roundtrip, qkeys = quantize_params(host, "int8")
    assert qkeys == ["head/w"]
    assert "head/w" + PARAMS_SCALE_SUFFIX in store
    assert np.array_equal(store["head/b"], host["head/b"])
    decoded = dequantize_params(store, "int8")
    assert sorted(decoded) == sorted(host)
    assert np.array_equal(decoded["head/w"], roundtrip["head/w"])
    assert np.array_equal(decoded["head/b"], host["head/b"])
    # round trip of the round trip is exact (the identity above)
    store2, roundtrip2, _ = quantize_params(
        {k: np.asarray(v) for k, v in roundtrip.items()}, "int8")
    assert np.array_equal(store2["head/w"], store["head/w"])


def test_fp8_storage_bytes_roundtrip(tmp_path):
    """fp8 codes persist as uint8 byte views (np.load loses the
    ml_dtypes dtype otherwise) and reconstruct bit-exactly through a
    real npz save/load."""
    from roc_tpu.serve.quant import (dequantize_rows, fp8_supported,
                                     from_storage_bytes,
                                     quantize_rows, to_storage_bytes)
    if not fp8_supported():
        pytest.skip("fp8-e4m3 unsupported in this stack")
    rng = np.random.RandomState(2)
    x = rng.randn(32, 16).astype(np.float32)
    q, s = quantize_rows(x, "fp8")
    p = str(tmp_path / "fp8.npz")
    np.savez(p, q=to_storage_bytes(q), s=s)
    z = np.load(p)
    q2 = from_storage_bytes(z["q"], "fp8")
    assert q2.dtype == q.dtype
    assert np.array_equal(q2.view(np.uint8), q.view(np.uint8))
    assert np.array_equal(dequantize_rows(q2, z["s"]),
                          dequantize_rows(q, s))


# ----------------------------------------------------- export / load

def test_export_cold_load_parity_int8(sgc_rig, tmp_path):
    """The tentpole acceptance: an int8 export passes the measured
    drift gate, records the table shrink in the manifest, and a cold
    load serves the gated values BIT-exactly (round-trip identity →
    identical device codes) with the same program keys."""
    from roc_tpu.serve.export import (build_predictor,
                                      export_predictor,
                                      load_predictor)
    ds, tr, ref = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="precomputed",
                           quant="int8")
    art = str(tmp_path / "artifact")
    manifest = export_predictor(
        pred, art, dataset_meta={"V": ds.graph.num_nodes})
    qb = manifest["quant"]
    assert qb["spec"]["mode"] == "int8"
    assert qb["drift"]["ok"], qb["drift"]
    assert qb["table"]["shrink"] >= 3.0, qb["table"]
    ids = np.arange(ds.graph.num_nodes)
    want = np.asarray(pred.query(ids))
    cold = load_predictor(art)
    assert cold.quant == "int8"
    got = np.asarray(cold.query(ids))
    assert np.array_equal(got, want), (
        f"cold load drifted from the gated values by "
        f"{np.abs(got - want).max()}")
    assert cold.program_keys() == manifest["program_keys"]
    # and the served values stay within the gate vs the fp32 trainer
    rel = np.abs(got - ref).max() / max(1.0, np.abs(ref).max())
    assert rel <= qb["drift"]["dlogit_max"], rel


def test_cold_server_zero_new_compiles_int8(tmp_path):
    """The zero-new-compiles acceptance holds for the QUANTIZED
    program set: an int8 export in one child, a cold server in
    another — no new serve entry in the persistent cache, and the
    worker's compile events stay inside the manifest's keys."""
    cache = str(tmp_path / "cache")
    os.makedirs(cache)
    art = str(tmp_path / "artifact")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache
    env["ROC_TPU_CACHE_MIN_SECS"] = "0"
    events = str(tmp_path / "events.jsonl")
    env["ROC_TPU_EVENTS"] = events
    code = (
        "import numpy as np, jax\n"
        "from roc_tpu.utils.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "from roc_tpu.core.graph import synthetic_dataset\n"
        "from roc_tpu.models.sgc import build_sgc\n"
        "from roc_tpu.train.trainer import Trainer, TrainConfig\n"
        "from roc_tpu.serve.export import export_trainer\n"
        "ds = synthetic_dataset(num_nodes=300, avg_degree=6, "
        "in_dim=24, num_classes=5, seed=0)\n"
        "tr = Trainer(build_sgc([24, 5], k=2, dropout_rate=0.5), ds, "
        "TrainConfig(verbose=False, symmetric=True))\n"
        f"export_trainer(tr, ds, {art!r}, quant='int8')\n"
        "print('EXPORT_OK')\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "EXPORT_OK" in r.stdout
    before = set(os.listdir(cache))
    r = subprocess.run([sys.executable, _WORKER, art],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WORKER_OK" in r.stdout
    new = set(os.listdir(cache)) - before
    new_serve = sorted(f for f in new if _SERVE_ENTRY.search(f)
                       and f.endswith("-cache"))
    assert not new_serve, (
        f"cold int8 server compiled NEW serve programs: {new_serve}")
    man = json.load(open(os.path.join(art, "serve_manifest.json")))
    assert man["quant"]["spec"]["mode"] == "int8"
    assert any("_q8:" in k for k in man["program_keys"]), (
        man["program_keys"])
    live = {json.loads(line).get("program_key")
            for line in open(events)
            if '"cat": "compile"' in line}
    live.discard(None)
    serve_live = {k for k in live if k.startswith("serve_")}
    assert serve_live <= set(man["program_keys"]), (
        f"live-only serve keys: "
        f"{sorted(serve_live - set(man['program_keys']))}")


# --------------------------------------------------- refresh / swap

def test_add_edges_requantize_exactness(sgc_rig):
    """Incremental invalidation requantizes ONLY the recomputed rows
    — and lands bit-equal (codes AND scales) to quantizing a full
    rebuild of the mutated graph's tables."""
    from roc_tpu.core.graph import Graph
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.propagation import PropagationCache
    from roc_tpu.serve.quant import quantize_rows
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="precomputed",
                           quant="int8")
    v0 = pred.published().version
    u, v = 3, 250
    n = pred.invalidate([u, v], [v, u])
    assert n > 0
    pub = pred.published()
    assert pub.version == v0 + 1 and pub.qmode == "int8"
    g2 = Graph(row_ptr=pred.cache.row_ptr.copy(),
               col_idx=pred.cache.col_idx.copy())
    rebuilt = PropagationCache.build(g2, pred.cache.ops,
                                     np.asarray(ds.features))
    # the host table is fp32 (exact invalidation layer)…
    assert np.abs(pred.cache.table - rebuilt.table).max() <= 1e-5
    # …and the device codes/scales equal a from-scratch quantization
    q_full, s_full = quantize_rows(
        np.asarray(pred.cache.table, dtype=np.float32), "int8")
    V = ds.graph.num_nodes
    assert np.array_equal(np.asarray(pub.table)[:V], q_full)
    assert np.array_equal(np.asarray(pub.scale)[:V], s_full)


def test_mid_rollout_publish_quant_bit_exact(sgc_rig):
    """``publish_quant('int8')`` mid-load: a dispatch pinned to the
    fp32 version keeps serving the fp32 program bit-exactly AFTER
    int8 publishes (quant-spec-pinned), new dispatches serve int8,
    and swapping back to fp32 restores the original values."""
    from roc_tpu.serve.export import build_predictor
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="precomputed")
    assert pred.quant == "off"
    ids = np.arange(8).astype(np.int32)   # one padded bucket
    pub0 = pred.published()
    want_off = np.asarray(pred.query_device(ids, pub=pub0))
    v1 = pred.publish_quant("int8")
    pub1 = pred.published()
    assert pub1.qmode == "int8" and pub1.version == v1
    assert pred.quant == "int8"
    # the pinned fp32 batch is unaffected by the live int8 version
    again = np.asarray(pred.query_device(ids, pub=pub0))
    assert np.array_equal(again, want_off)
    # new dispatches serve the quantized program — close, not equal
    got_q8 = np.asarray(pred.query_device(ids, pub=pub1))
    rel = (np.abs(got_q8 - want_off).max()
           / max(1.0, np.abs(want_off).max()))
    assert 0.0 < rel <= 0.05, rel
    # rolling BACK re-publishes fp32 bit-exactly
    pred.publish_quant("off")
    back = np.asarray(pred.query_device(
        ids, pub=pred.published()))
    assert np.array_equal(back, want_off)


# ------------------------------------------------------- refusals

def test_export_refuses_on_drift(sgc_rig, tmp_path):
    """A quantization past the (here: impossible) thresholds REFUSES
    before any file is written — a drifting table never becomes an
    artifact."""
    from roc_tpu.serve.export import build_predictor, export_predictor
    from roc_tpu.serve.quant import QuantDriftError
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="precomputed",
                           quant="int8")
    art = str(tmp_path / "refused")
    with pytest.raises(QuantDriftError) as ei:
        export_predictor(pred, art, drift_dlogit_max=1e-12)
    assert "drift" in str(ei.value)
    assert not os.path.exists(art), "refusal must precede any write"


def test_invalidate_scale_guard_refuses(sgc_rig):
    """Refreshed rows whose scale blows the envelope pinned at gate
    time refuse (QuantDriftError) — and the OLD version stays
    published, still serving."""
    from roc_tpu.serve.export import build_predictor
    from roc_tpu.serve.quant import QuantDriftError
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="precomputed",
                           quant="int8")
    pub0 = pred.published()
    want = np.asarray(pred.query(np.arange(8)))
    pred._scale_guard = 1e-12     # simulate a poisoned envelope
    with pytest.raises(QuantDriftError):
        pred.invalidate([3, 250], [250, 3])
    assert pred.published().version == pub0.version
    assert np.array_equal(np.asarray(pred.query(np.arange(8))), want)


def test_fp8_export_behind_relaxed_gate(sgc_rig, tmp_path):
    """fp8-e4m3 (3 mantissa bits) drifts genuinely more than int8 —
    exporting it requires DELIBERATELY relaxed thresholds, and then
    cold-load parity holds exactly like int8."""
    from roc_tpu.serve.export import (build_predictor,
                                      export_predictor,
                                      load_predictor)
    from roc_tpu.serve.quant import fp8_supported
    if not fp8_supported():
        pytest.skip("fp8-e4m3 unsupported in this stack")
    ds, tr, _ = sgc_rig
    pred = build_predictor(_sgc_model(), ds, _config(),
                           params=tr.params, backend="precomputed",
                           quant="fp8")
    art = str(tmp_path / "fp8_art")
    manifest = export_predictor(
        pred, art, drift_argmax_min=0.90, drift_dlogit_max=0.20)
    assert manifest["quant"]["spec"]["mode"] == "fp8"
    ids = np.arange(ds.graph.num_nodes)
    want = np.asarray(pred.query(ids))
    cold = load_predictor(art)
    assert cold.quant == "fp8"
    assert np.array_equal(np.asarray(cold.query(ids)), want)

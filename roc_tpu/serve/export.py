"""Exported predictor artifacts: ``python -m roc_tpu.export``.

The export step is where serving's cold-start cost is paid, once,
off the request path:

1. resolve the model + config through the SAME
   ``train/trainer.resolve_config`` pass training uses (fuse rewrite,
   impl auto-resolution, attention policy) — the artifact records the
   RESOLVED state, so a server can never re-resolve differently;
2. for the fixed-propagation family, materialize the propagation
   table (``serve/propagation.py`` — streamed through the
   ``StagingPool`` machinery, so >HBM graphs export the way they
   train);
3. AOT-compile every bucketed serve program into the persistent
   compile cache (``utils/prewarm.warm_candidates``, with its
   warm-vs-cold accounting) and assert
   warm-hit parity with a second pass;
4. write ``serve_manifest.json`` — program keys, quantized buckets,
   the resolved model op list (``Model.to_spec``), and the model
   fingerprint reusing checkpoint v2's strict half
   (``utils/checkpoint.params_signature``) — next to ``params.npz``
   and ``propagation.npz``.

A cold server process (``load_predictor`` + ``serve/server.py``) then
reaches first-query readiness with ZERO new compiles: its programs
are keyed identically to the export-time warm set (asserted in
tests/test_serve.py).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.ell import AGGR_IMPLS, check_stored_aggr_impl
from ..obs.events import emit
from .predictor import SERVE_BUCKETS, Predictor, ShardSlice
from .propagation import (PropagationCache, logits_table_cache,
                          prefix_descriptors)

MANIFEST_NAME = "serve_manifest.json"
MANIFEST_VERSION = 1

SHARD_FILE = "propagation_shard{k}.npz"


def _host_params(params) -> Dict[str, np.ndarray]:
    import jax
    # export-time persistence fetch, not a request-path sync
    return {k: np.asarray(jax.device_get(v))  # roc-lint: ok=host-sync-hot-path
            for k, v in params.items()}


def resolve_backend(model, backend: str) -> Tuple[str, Optional[str]]:
    """``(backend, flavor)``: 'auto' picks 'precomputed' (flavor
    'akx') when the model has a parameter-free propagation prefix
    (``Model.precompute_split`` — the SGC family), else 'full'.  An
    explicit 'precomputed' on a model without the split serves the
    frozen full-forward logits instead (flavor 'table' — the
    decoupled APPNP shape)."""
    has_split = model.precompute_split() is not None
    if backend == "auto":
        return (("precomputed", "akx") if has_split else ("full", None))
    if backend == "precomputed":
        return ("precomputed", "akx" if has_split else "table")
    if backend == "full":
        return ("full", None)
    raise ValueError(f"unknown serve backend {backend!r}; expected "
                     "'auto', 'precomputed', or 'full'")


def _full_gctx(model, dataset, config):
    from ..train.trainer import make_graph_context
    return make_graph_context(
        dataset, config.aggr_impl, config.chunk,
        symmetric=config.symmetric,
        sect_sub_w=config.sect_sub_w, sect_u16=config.sect_u16,
        bdense_min_fill=config.bdense_min_fill,
        bdense_a_budget=config.bdense_a_budget,
        bdense_group=config.bdense_group,
        verbose=config.verbose,
        fuse=model.num_fused_aggregates() > 0,
        head_chunk=0)


def _num_classes(model) -> Optional[int]:
    dims = [op.dim for op in model._ops if op.kind == "linear"]
    return dims[-1] if dims else None


def _full_logits_host(model, dataset, config, params) -> np.ndarray:
    """The frozen full-forward logits — the 'table' flavor's
    precompute.  Runs the eval forward ONCE at export (this program is
    export-time-only; it is deliberately not part of the audited serve
    set)."""
    import jax
    import jax.numpy as jnp

    from ..train.trainer import cast_floats, compute_dtype_of
    gctx = _full_gctx(model, dataset, config)
    compute = compute_dtype_of(config)
    feats = jnp.asarray(dataset.features, dtype=compute)

    logits = jax.jit(
        lambda p, f, g: model.apply(cast_floats(p, compute), f, g,
                                    key=None, train=False)
    )(params, feats, gctx)
    # export-time precompute fetch, not a request-path sync
    return np.asarray(jax.device_get(logits),  # roc-lint: ok=host-sync-hot-path
                      dtype=np.float32)


# a typed model's serving export is out of scope (ROADMAP, Reach): the
# predictor's gather paths, propagation cache and shard plan know one
# index space, one weight a layer and no trainable input tables
TYPED_REFUSAL = (
    "a typed graph's model (--model rgcn: embed_<k> tables, a weight "
    "a relation) has no serving export: serve/export.py and the "
    "predictor know one homogeneous graph")
_TYPED_PARAMS = ("embed_", "rel0_", "root0_")
# a model with batch statistics (ROADMAP R14): the export's forwards
# cast every entry of the parameter dict to the compute dtype and its
# quantized tables know weights, not running statistics; folding a
# ``batch_norm`` into the neighbouring ``linear`` at export time is the
# way in, and is not built
STATE_REFUSAL = (
    "a model with batch statistics (--model deepergcn: batch_norm's "
    "running mean and variance ride in the parameter dict as float32 "
    "state) has no serving export: serve/export.py casts and quantizes "
    "every entry as a weight")
# a model with dot-product attention: the predictor's cached propagation
# and quantized tables hold a layer's weights and a fixed neighbour sum,
# and neither a per-row LayerNorm nor the per-row gate between the
# attention and its root path folds into them
DOT_ATTENTION_REFUSAL = (
    "a model with dot-product attention (--model gtrans: "
    "transformer_attention's gated root path and layer_norm) has no "
    "serving export: serve/export.py cannot fold LayerNorm or the gate "
    "into its quantized tables")


def build_predictor(model, dataset, config, params=None,
                    backend: str = "auto",
                    buckets: Sequence[int] = SERVE_BUCKETS,
                    cache: Optional[PropagationCache] = None,
                    quant: str = "off",
                    verbose: bool = False) -> Predictor:
    """Resolve + build a live Predictor.  ``params=None`` initializes
    fresh weights (rig/benchmark use); ``cache`` short-circuits the
    propagation precompute (the artifact loader passes the persisted
    one — live builds compute it here).  ``quant`` selects the serving
    table encoding (``serve/quant.py``); the drift GATE lives in
    :func:`export_predictor` — a live build is ungated rehearsal."""
    import jax

    from ..train.trainer import (resolve_config, resolve_symmetric)
    import dataclasses
    if model.uses_relations():
        raise NotImplementedError(TYPED_REFUSAL)
    if model.state_names():
        raise NotImplementedError(STATE_REFUSAL)
    if model.uses_dot_attention():
        raise NotImplementedError(DOT_ATTENTION_REFUSAL)
    model, config, _ = resolve_config(model, dataset, config)
    config = dataclasses.replace(
        config, symmetric=resolve_symmetric(dataset, config.symmetric))
    if params is None:
        params = model.init_params(jax.random.PRNGKey(config.seed),
                                   dtype=config.dtype)
    backend, flavor = resolve_backend(model, backend)
    head_model = None
    gctx = None
    if backend == "precomputed":
        if flavor == "akx":
            prefix_ops, head_model = model.precompute_split()
            if cache is None:
                cache = PropagationCache.build(
                    dataset.graph, prefix_descriptors(prefix_ops),
                    np.asarray(dataset.features))
        elif cache is None:
            cache = logits_table_cache(
                _full_logits_host(model, dataset, config, params))
    else:
        gctx = _full_gctx(model, dataset, config)
    emit("serve", f"predictor: backend={backend}"
         + (f"/{flavor}" if flavor else "")
         + f" buckets={tuple(sorted(buckets))} V={dataset.graph.num_nodes}",
         console=verbose, kind="build", backend=backend, flavor=flavor)
    return Predictor(model, config, params, backend, buckets,
                     cache=cache, head_model=head_model, flavor=flavor,
                     dataset=dataset if backend == "full" else None,
                     gctx=gctx, num_classes=_num_classes(model),
                     quant=quant, verbose=verbose)


# ------------------------------------------------------- sharded slices

def make_shard_slices(cache: PropagationCache, num_shards: int,
                      buckets: Sequence[int],
                      quant: str = "off") -> List[ShardSlice]:
    """The export-time shard PLAN (PR 20): contiguous ``[lo, hi)``
    vertex ranges from the trainer's own edge-balanced sweep
    (``core/partition.edge_balanced_bounds`` — serve slices inherit
    training's partition law), under ONE fleet-uniform padded layout:
    ``rows_padded`` = max owned rows snapped to NODE_MULTIPLE, ``halo``
    = the largest serve bucket (a microbatch's foreign rows always
    fit).  Quantized slices are cut from the FULL table's ``(codes,
    scales)`` — per-row symmetric quantization is row-local, so slice
    codes are bit-identical to the unsharded artifact's — and every
    slice carries the full-table scale envelope so refresh guarding
    matches the export drift gate's measurement."""
    from ..core.partition import NODE_MULTIPLE, edge_balanced_bounds
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    V = cache.num_nodes
    plan: List[Tuple[int, int]] = []
    for left, right in edge_balanced_bounds(cache.row_ptr, num_shards):
        plan.append((int(left), int(right) + 1) if right >= left
                    else (V, V))
    own_max = max(hi - lo for lo, hi in plan)
    rows_padded = -(-max(own_max, 1)
                    // NODE_MULTIPLE) * NODE_MULTIPLE
    halo = max(int(b) for b in buckets)
    if quant != "off":
        from .quant import quantize_rows
        q, sc = quantize_rows(cache.table, quant)
        # host numpy scale max at EXPORT time, not a device fetch
        guard = float(sc.max())  # roc-lint: ok=host-sync-hot-path
        return [ShardSlice(lo, hi, V, rows_padded, halo,
                           codes=q[lo:hi], scales=sc[lo:hi],
                           scale_guard=guard) for lo, hi in plan]
    return [ShardSlice(lo, hi, V, rows_padded, halo,
                       rows=cache.table[lo:hi]) for lo, hi in plan]


def _write_shard_slice(out_dir: str, k: int, sl: ShardSlice,
                       quant: str) -> str:
    import tempfile
    data: Dict[str, Any] = {
        "lo": np.int64(sl.lo), "hi": np.int64(sl.hi),
        "num_nodes": np.int64(sl.num_nodes),
        "rows_padded": np.int64(sl.rows_padded),
        "halo": np.int64(sl.halo)}
    if quant != "off":
        from .quant import to_storage_bytes
        data["rows_q"] = to_storage_bytes(sl.codes)
        data["rows_scale"] = sl.scales
        data["scale_guard"] = np.float64(sl.scale_guard)
    else:
        data["rows"] = sl.rows
    path = os.path.join(out_dir, SHARD_FILE.format(k=k))
    fd, tmp = tempfile.mkstemp(dir=out_dir, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **data)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path


def load_shard_slice(artifact_dir: str, k: int,
                     quant: str = "off") -> ShardSlice:
    """One persisted table slice → :class:`ShardSlice` (quantized
    slices rebuild codes from storage-byte views, bit-exact)."""
    path = os.path.join(artifact_dir, SHARD_FILE.format(k=k))
    with np.load(path) as z:
        lo, hi = int(z["lo"]), int(z["hi"])
        num_nodes = int(z["num_nodes"])
        rows_padded, halo = int(z["rows_padded"]), int(z["halo"])
        if quant != "off":
            from .quant import from_storage_bytes
            return ShardSlice(
                lo, hi, num_nodes, rows_padded, halo,
                codes=from_storage_bytes(z["rows_q"], quant),
                scales=np.asarray(z["rows_scale"], dtype=np.float32),
                # npz scalar at cold-load time, not a device fetch
                scale_guard=float(z["scale_guard"]))  # roc-lint: ok=host-sync-hot-path
        return ShardSlice(lo, hi, num_nodes, rows_padded, halo,
                          rows=np.asarray(z["rows"],
                                          dtype=np.float32))


def _shard_view_predictor(pred: Predictor,
                          sl: ShardSlice) -> Predictor:
    """A shard-view Predictor over the SAME resolved model/params —
    export warms its bucket programs once (one fleet-uniform table
    shape → one program set shared by every shard), and
    ``load_predictor(shard=k)`` rebuilds the identical keys."""
    return Predictor(pred.model, pred.config, pred.params,
                     "precomputed", pred.buckets, cache=None,
                     head_model=pred.head_model, flavor=pred.flavor,
                     num_classes=pred.num_classes, quant=pred.quant,
                     shard=sl, verbose=pred.verbose)


# ------------------------------------------------------------ artifact

def _quant_ref_logits(pred: Predictor, params, sample) -> np.ndarray:
    """The fp32 half of the drift gate: fp32 table rows + the
    UNquantized params through the same head.  Export-time-only
    program, deliberately outside the audited serve set (the
    ``_full_logits_host`` precedent)."""
    import jax
    import jax.numpy as jnp

    from ..train.trainer import cast_floats
    rows = pred.cache.table[sample]
    if pred.flavor == "table":
        return np.asarray(rows, dtype=np.float32)
    x = jnp.asarray(rows, dtype=pred.compute)
    out = jax.jit(
        lambda p, v, g: pred.head_model.apply(
            cast_floats(p, pred.compute), v, g, key=None, train=False)
    )(params, x, pred._gctx)
    # export-time gate fetch, not a request-path sync
    return np.asarray(jax.device_get(out),  # roc-lint: ok=host-sync-hot-path
                      dtype=np.float32)


def export_predictor(pred: Predictor, out_dir: str,
                     dataset_meta: Optional[Dict[str, Any]] = None,
                     cache_dir: Optional[str] = None,
                     verify_warm: bool = True,
                     drift_argmax_min: Optional[float] = None,
                     drift_dlogit_max: Optional[float] = None,
                     shards: int = 0
                     ) -> Dict[str, Any]:
    """Persist ``pred`` as a serving artifact and pre-pay its compile
    wall: params + propagation tables + manifest on disk, every bucket
    program AOT-compiled into the persistent cache.  With
    ``verify_warm`` a second AOT pass asserts every program is now a
    warm hit — the prewarm-parity guarantee the manifest's
    ``program_keys`` advertise.  Returns the manifest dict.

    A quantized predictor additionally runs the measured accuracy
    drift gate BEFORE any file is written: argmax agreement + max
    |Δlogit| vs the fp32 reference on a held-out node sample, with
    :class:`roc_tpu.serve.quant.QuantDriftError` refusal past the
    thresholds (CLI-adjustable; defaults in ``serve/quant.py``) —
    a drifting quantization never becomes an artifact."""
    from ..utils.checkpoint import params_signature
    import jax.numpy as jnp
    host_params = _host_params(pred.params)
    from .quant import QuantSpec
    qblock: Dict[str, Any] = {"spec": QuantSpec(pred.quant).to_json()}
    store_params = host_params
    if pred.quant != "off":
        from ..train.trainer import compute_dtype_of
        from .quant import (drift_report, drift_sample,
                            quantize_params, require_drift_ok,
                            row_scales, scale_stats)
        params_orig = pred.params
        store_params, roundtrip, qkeys = quantize_params(
            host_params, pred.quant)
        # the export-time predictor must serve the exact values a
        # cold load reconstructs: swap in the dequantize∘quantize
        # round trip (structural fingerprint unchanged)
        pred.params = {k: jnp.asarray(v)
                       for k, v in roundtrip.items()}
        sample = drift_sample(pred.num_nodes)
        drift = drift_report(
            _quant_ref_logits(pred, params_orig, sample),
            pred.query(sample),
            **{k: v for k, v in
               (("argmax_min", drift_argmax_min),
                ("dlogit_max", drift_dlogit_max)) if v is not None})
        qblock["drift"] = drift
        qblock["params"] = {"quantized": qkeys,
                            "scale_suffix": "::scale"}
        qblock["scale_stats"] = [scale_stats(row_scales(s, pred.quant))
                                 for s in pred.cache.stages]
        require_drift_ok(drift, f"export to {out_dir}")
    if pred.cache is not None:
        from .quant import table_bytes
        shapes = [s.shape for s in pred.cache.stages]
        b_fp32 = sum(table_bytes(s, "off") for s in shapes)
        b_mode = sum(table_bytes(s, pred.quant) for s in shapes)
        qblock["table"] = {
            "stages": len(shapes),
            "bytes_fp32": int(b_fp32),
            "bytes": int(b_mode),
            "shrink": round(b_fp32 / max(b_mode, 1), 2)}
    os.makedirs(out_dir, exist_ok=True)
    np.savez(os.path.join(out_dir, "params.npz"), **store_params)
    if pred.cache is not None:
        pred.cache.save(os.path.join(out_dir, "propagation.npz"),
                        quant=pred.quant)
    shard_block: Optional[Dict[str, Any]] = None
    if shards:
        # sliced artifacts (PR 20): per-shard table slices under one
        # fleet-uniform padded shape, warmed ONCE through a shard-view
        # predictor — every shard's cold load then hits the same
        # program set with zero new compiles
        if pred.backend != "precomputed" or pred.cache is None:
            raise ValueError("sharded export applies to the "
                             "precomputed table backend")
        from .quant import table_bytes
        slices = make_shard_slices(pred.cache, shards, pred.buckets,
                                   pred.quant)
        files = [os.path.basename(
            _write_shard_slice(out_dir, k, sl, pred.quant))
            for k, sl in enumerate(slices)]
        spred = _shard_view_predictor(pred, slices[0])
        swarm = spred.warm(cache_dir=cache_dir,
                           name="serve_export_shard")
        if swarm.get("failed"):
            raise RuntimeError(
                f"sharded export: {swarm['failed']} shard-view "
                f"program(s) failed to AOT-compile — a sliced cold "
                f"load would compile at first query")
        F = int(pred.cache.table.shape[1])
        shard_block = {
            "n": int(shards),
            "plan": [[int(sl.lo), int(sl.hi)] for sl in slices],
            "rows_padded": int(slices[0].rows_padded),
            "halo": int(slices[0].halo),
            "files": files,
            # the capacity math the fleet view reads:
            # per-replica bytes are O(V/N) + halo, vs O(V) full
            "bytes_per_replica": int(table_bytes(
                (slices[0].rows_padded + slices[0].halo + 1, F),
                pred.quant)),
            "bytes_full": int(table_bytes(
                (pred.num_nodes + 1, F), pred.quant)),
            "program_keys": spred.program_keys(),
            "prewarm": {k: swarm.get(k) for k in
                        ("programs", "compile_warm_hits",
                         "compile_cold", "failed", "prewarm_s")},
        }
    cfg = pred.config
    manifest: Dict[str, Any] = {
        "version": MANIFEST_VERSION,
        "backend": pred.backend,
        "flavor": pred.flavor,
        "buckets": list(pred.buckets),
        "model": pred.model.to_spec(),
        "num_classes": pred.num_classes,
        "config": {
            "dtype": str(jnp.dtype(cfg.dtype)),
            "compute_dtype": (None if cfg.compute_dtype is None
                              else str(jnp.dtype(cfg.compute_dtype))),
            "aggr_impl": cfg.aggr_impl, "chunk": cfg.chunk,
            "symmetric": bool(cfg.symmetric),
            "sect_sub_w": cfg.sect_sub_w, "sect_u16": cfg.sect_u16,
            "bdense_min_fill": cfg.bdense_min_fill,
            "bdense_a_budget": cfg.bdense_a_budget,
            "bdense_group": cfg.bdense_group,
        },
        # checkpoint v2's strict half, reused verbatim: a server can
        # hold an artifact against the checkpoint lineage it claims
        "fingerprint": {
            "params_sig": params_signature(host_params),
            "dtype": str(jnp.dtype(cfg.dtype)),
            "compute_dtype": (None if cfg.compute_dtype is None
                              else str(jnp.dtype(cfg.compute_dtype))),
            "dataset": dict(dataset_meta or {}),
        },
        "dataset": dict(dataset_meta or {}),
        "num_nodes": pred.num_nodes,
        "program_keys": pred.program_keys(),
        "quant": qblock,
        "shards": shard_block,
        "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
    warm = pred.warm(cache_dir=cache_dir, name="serve_export")
    manifest["prewarm"] = {k: warm.get(k) for k in
                          ("programs", "compile_warm_hits",
                           "compile_cold", "failed", "prewarm_s")}
    if warm.get("failed"):
        raise RuntimeError(
            f"serve export: {warm['failed']} program(s) failed to "
            f"AOT-compile — the artifact would cold-compile at first "
            f"query; see the compile events")
    if verify_warm:
        check = pred.warm(cache_dir=cache_dir, name="serve_verify")
        manifest["prewarm"]["verified_warm_hits"] = \
            check.get("compile_warm_hits")
        if check.get("compile_warm_hits") != check.get("programs"):
            raise RuntimeError(
                f"serve export warm-hit parity FAILED: "
                f"{check.get('compile_warm_hits')} of "
                f"{check.get('programs')} programs warm on the second "
                f"pass — the persistent cache is not serving the "
                f"programs just compiled (unstable cache key?)")
    path = os.path.join(out_dir, MANIFEST_NAME)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    emit("serve", f"artifact exported to {out_dir}: {pred.backend}"
         + (f"/{pred.flavor}" if pred.flavor else "")
         + f", {len(manifest['program_keys'])} programs "
         f"({manifest['prewarm']['compile_warm_hits']} warm/"
         f"{manifest['prewarm']['compile_cold']} cold)",
         kind="export", path=out_dir, backend=pred.backend)
    return manifest


def export_trainer(trainer, dataset, out_dir: str,
                   backend: str = "auto",
                   buckets: Sequence[int] = SERVE_BUCKETS,
                   cache_dir: Optional[str] = None,
                   verify_warm: bool = True,
                   quant: str = "off") -> Dict[str, Any]:
    """Export a LIVE trainer's weights as a serving artifact — works
    for both ``Trainer`` and ``DistributedTrainer`` (replicated params
    fetch identically); the trainer's model/config are already
    resolved, and ``resolve_config`` is idempotent, so the artifact
    records exactly what trained."""
    pred = build_predictor(
        trainer.model, dataset, trainer.config,
        params=trainer.params, backend=backend, buckets=buckets,
        quant=quant)
    meta = {"V": int(dataset.graph.num_nodes),
            "E": int(dataset.graph.num_edges),
            "name": getattr(dataset, "name", None)}
    return export_predictor(pred, out_dir, dataset_meta=meta,
                            cache_dir=cache_dir,
                            verify_warm=verify_warm)


def load_predictor(artifact_dir: str, dataset=None,
                   verbose: bool = False,
                   shard: Optional[int] = None) -> Predictor:
    """Rebuild a Predictor from an exported artifact — the cold-server
    path.  No resolve pass runs here: the manifest carries the
    RESOLVED model op list and config fields, so the programs built
    are keyed identically to the export-time warm set.  ``dataset`` is
    required for the full-graph backend only (precomputed artifacts
    are self-contained).

    ``shard=k`` cold-loads ONE table slice of a sharded artifact
    (``export --shards N``): O(V/N)+halo table bytes instead of O(V),
    same global id space, program keys identical to the export-time
    shard-view warm set (zero new compiles on any shard) — ids the
    slice does not own are served through the cross-shard gather leg
    once the caller wires ``pred.gather_fn``."""
    import jax.numpy as jnp

    from ..models.builder import Model
    from ..train.trainer import TrainConfig
    from ..utils.checkpoint import params_signature
    with open(os.path.join(artifact_dir, MANIFEST_NAME)) as f:
        manifest = json.load(f)
    if manifest.get("version") != MANIFEST_VERSION:
        raise ValueError(
            f"{artifact_dir}: manifest version "
            f"{manifest.get('version')} != {MANIFEST_VERSION}")
    model = Model.from_spec(manifest["model"])
    mc = manifest["config"]
    check_stored_aggr_impl(mc["aggr_impl"], artifact_dir)
    config = TrainConfig(
        verbose=verbose, memory="manual", aggr_fuse="off",
        dtype=jnp.dtype(mc["dtype"]),
        compute_dtype=(None if mc["compute_dtype"] is None
                       else jnp.dtype(mc["compute_dtype"])),
        aggr_impl=mc["aggr_impl"], chunk=mc["chunk"],
        symmetric=mc["symmetric"], sect_sub_w=mc["sect_sub_w"],
        sect_u16=mc["sect_u16"],
        bdense_min_fill=mc["bdense_min_fill"],
        bdense_a_budget=mc["bdense_a_budget"],
        bdense_group=mc["bdense_group"])
    qmode = ((manifest.get("quant") or {}).get("spec")
             or {}).get("mode", "off")
    with np.load(os.path.join(artifact_dir, "params.npz")) as z:
        raw = {k: np.asarray(z[k]) for k in z.files}
    if qmode != "off":
        # storage-byte views + ::scale companions → fp32, then cast
        # like any params load; the fingerprint is structural, so the
        # reconstructed tree hashes identically to the exported one
        from .quant import dequantize_params
        raw = dequantize_params(raw, qmode)
    params = {k: jnp.asarray(v, dtype=config.dtype)
              for k, v in raw.items()}
    sig = params_signature(params)
    want = (manifest.get("fingerprint") or {}).get("params_sig")
    if want and sig != want:
        raise ValueError(
            f"{artifact_dir}: params fingerprint mismatch ({sig} != "
            f"manifest {want}) — params.npz does not belong to this "
            f"manifest")
    backend, flavor = manifest["backend"], manifest.get("flavor")
    cache = None
    head_model = None
    gctx = None
    slice_ = None
    if shard is not None:
        sb = manifest.get("shards")
        if not sb:
            raise ValueError(
                f"{artifact_dir}: shard={shard} requested but the "
                f"artifact was not exported with --shards")
        if not (0 <= int(shard) < int(sb["n"])):
            raise ValueError(
                f"{artifact_dir}: shard {shard} out of range "
                f"[0, {sb['n']})")
        slice_ = load_shard_slice(artifact_dir, int(shard), qmode)
        if flavor == "akx":
            head_model = model.precompute_split()[1]
    elif backend == "precomputed":
        cache = PropagationCache.load(
            os.path.join(artifact_dir, "propagation.npz"))
        if flavor == "akx":
            head_model = model.precompute_split()[1]
    else:
        if dataset is None:
            raise ValueError(
                "full-graph serving needs the dataset (the graph is "
                "not part of the artifact); pass dataset=")
        want_v = int(manifest["num_nodes"])
        want_e = (manifest.get("dataset") or {}).get("E")
        if int(dataset.graph.num_nodes) != want_v or (
                want_e is not None
                and int(dataset.graph.num_edges) != int(want_e)):
            raise ValueError(
                f"dataset V={dataset.graph.num_nodes}/"
                f"E={dataset.graph.num_edges} != artifact "
                f"V={want_v}/E={want_e} — full-graph serving on a "
                f"different graph than the export would be silently "
                f"wrong")
        gctx = _full_gctx(model, dataset, config)
    pred = Predictor(model, config, params, backend,
                     manifest["buckets"], cache=cache,
                     head_model=head_model, flavor=flavor,
                     dataset=dataset if backend == "full" else None,
                     gctx=gctx,
                     num_classes=manifest.get("num_classes"),
                     quant=qmode, shard=slice_, verbose=verbose)
    # a sliced load's programs must match the export-time SHARD-VIEW
    # warm set (one fleet-uniform table shape → one key set shared by
    # every shard); full loads match the top-level keys
    want_keys = (manifest["shards"]["program_keys"]
                 if shard is not None
                 else manifest.get("program_keys"))
    live = pred.program_keys()
    if sorted(want_keys or []) != live:
        raise ValueError(
            f"{artifact_dir}: rebuilt program keys differ from the "
            f"manifest — this server would cold-compile; re-export "
            f"(manifest {len(want_keys or [])} vs "
            f"live {len(live)})")
    return pred


# ----------------------------------------------------------------- CLI

def parse_args(argv: Optional[List[str]] = None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m roc_tpu.export", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--out", required=True,
                    help="artifact directory (created)")
    ap.add_argument("--checkpoint", default=None,
                    help="training checkpoint (v3 directory or "
                         "legacy .npz) to export; "
                         "omitted = fresh Glorot weights (latency "
                         "rehearsal only — the export says so loudly)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "precomputed", "full"],
                    help="'auto' = precomputed propagation for the "
                         "fixed-propagation family (SGC shape), full-"
                         "graph recompute otherwise")
    ap.add_argument("--buckets", default=None,
                    help="comma list of microbatch buckets (default "
                         f"{','.join(str(b) for b in SERVE_BUCKETS)})")
    ap.add_argument("--model", default="gcn",
                    choices=["gcn", "sage", "gin", "gat", "sgc",
                             "appnp", "gcn2"])
    ap.add_argument("-layers", default="16-16-4",
                    help="dash-separated dims (train/cli.py "
                         "convention)")
    ap.add_argument("--hops", type=int, default=None)
    ap.add_argument("--alpha", type=float, default=None)
    ap.add_argument("--lam", type=float, default=None)
    ap.add_argument("--star", action="store_true",
                    help="for --model gcn2: the GCNII* form "
                         "(train/cli.py --star)")
    ap.add_argument("--heads", type=int, default=1)
    ap.add_argument("-dropout", type=float, default=0.5)
    ap.add_argument("-seed", type=int, default=1)
    ap.add_argument("-file", default=None, dest="file",
                    help="dataset prefix (default: the synthetic "
                         "smoke dataset, matching the training CLI)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "mixed"])
    ap.add_argument("--impl", default="auto", choices=AGGR_IMPLS)
    ap.add_argument("--fuse", default="auto",
                    choices=["auto", "on", "off"])
    ap.add_argument("--quantize", default="off",
                    choices=["off", "int8", "fp8"],
                    help="serving-table/params quantization "
                         "(symmetric per-row, scales alongside; int8 "
                         "is the portable floor, fp8-e4m3 where jax "
                         "supports it).  Export runs the accuracy "
                         "drift gate and REFUSES past the thresholds")
    ap.add_argument("--drift-argmax-min", type=float, default=None,
                    help="drift gate: minimum argmax agreement vs the "
                         "fp32 reference (default in serve/quant.py)")
    ap.add_argument("--drift-dlogit-max", type=float, default=None,
                    help="drift gate: maximum |Δlogit| vs the fp32 "
                         "reference (default in serve/quant.py)")
    ap.add_argument("--shards", type=int, default=0,
                    help="also write N per-shard propagation slices "
                         "+ a shard manifest block (edge-balanced "
                         "[lo,hi) plan, fleet-uniform padded shape); "
                         "a replica then cold-loads ONE slice "
                         "(load_predictor(shard=k)) at O(V/N)+halo "
                         "table bytes")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compile cache dir (default: "
                         "$JAX_COMPILATION_CACHE_DIR, which also wins "
                         "over this flag, else <repo>/.jax_cache)")
    ap.add_argument("--no-verify-warm", action="store_true",
                    help="skip the second AOT pass that asserts "
                         "warm-hit parity")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--events", default=None)
    ap.add_argument("-v", "--verbose", action="store_true")
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    import sys
    args = parse_args(argv)
    if args.events:
        os.environ["ROC_TPU_EVENTS"] = args.events
        from ..obs.events import configure
        configure(jsonl_path=args.events)
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    layers = [int(x) for x in args.layers.split("-")]
    if len(layers) < 2:
        print("error: -layers needs at least in-dim and classes",
              file=sys.stderr)
        return 2
    from ..core.graph import load_dataset, synthetic_dataset
    from ..models import model_builders
    from ..train.trainer import TrainConfig, resolve_dtypes
    if args.file:
        ds = load_dataset(args.file, in_dim=layers[0],
                          num_classes=layers[-1])
    else:
        ds = synthetic_dataset(512, 8, in_dim=layers[0],
                               num_classes=layers[-1], seed=args.seed)
    kwargs: Dict[str, Any] = {}
    if args.model == "gat":
        kwargs["heads"] = args.heads
    if args.model in ("sgc", "appnp"):
        kwargs["k"] = (args.hops if args.hops is not None
                       else (2 if args.model == "sgc" else 10))
    if args.model in ("appnp", "gcn2"):
        kwargs["alpha"] = args.alpha if args.alpha is not None else 0.1
    if args.model == "gcn2":
        kwargs["lam"] = args.lam if args.lam is not None else 0.5
        kwargs["star"] = args.star
    model = model_builders()[args.model](
        layers, dropout_rate=args.dropout, **kwargs)
    dt, cdt = resolve_dtypes(args.dtype)
    config = TrainConfig(verbose=args.verbose, seed=args.seed,
                         aggr_impl=args.impl, aggr_fuse=args.fuse,
                         dtype=dt, compute_dtype=cdt)
    params = None
    if args.checkpoint:
        from ..utils.checkpoint import restore_params_only
        params, fp, epoch = restore_params_only(args.checkpoint)
        if any(k.startswith(_TYPED_PARAMS) for k in params):
            print(f"error: {args.checkpoint}: {TYPED_REFUSAL}",
                  file=sys.stderr)
            return 2
        strict = (fp or {}).get("strict") or {}
        import jax.numpy as jnp
        if strict.get("dtype") and \
                strict["dtype"] != str(jnp.dtype(dt)):
            print(f"error: checkpoint dtype {strict['dtype']} != "
                  f"--dtype {jnp.dtype(dt)} — export with the "
                  f"training dtype", file=sys.stderr)
            return 2
        emit("serve", f"weights from {args.checkpoint} (epoch "
             f"{epoch})", kind="restore", epoch=epoch)
        params = {k: jnp_cast(v, dt) for k, v in params.items()}
    else:
        emit("serve", "no --checkpoint: exporting FRESH Glorot "
             "weights (latency rehearsal, not a trained model)",
             kind="fresh_params")
    buckets = (SERVE_BUCKETS if not args.buckets
               else tuple(int(b) for b in args.buckets.split(",")))
    pred = build_predictor(model, ds, config, params=params,
                           backend=args.backend, buckets=buckets,
                           quant=args.quantize, verbose=args.verbose)
    meta = {"V": int(ds.graph.num_nodes),
            "E": int(ds.graph.num_edges),
            "name": getattr(ds, "name", None),
            "prefix": args.file}
    manifest = export_predictor(pred, args.out, dataset_meta=meta,
                                cache_dir=args.cache_dir,
                                verify_warm=not args.no_verify_warm,
                                drift_argmax_min=args.drift_argmax_min,
                                drift_dlogit_max=args.drift_dlogit_max,
                                shards=args.shards)
    print(json.dumps({
        "artifact": args.out, "backend": manifest["backend"],
        "flavor": manifest["flavor"],
        "programs": len(manifest["program_keys"]),
        "buckets": manifest["buckets"],
        "quant": manifest["quant"],
        "shards": (None if not manifest.get("shards") else
                   {k: manifest["shards"][k] for k in
                    ("n", "plan", "bytes_per_replica",
                     "bytes_full")}),
        "prewarm": manifest["prewarm"]}))
    return 0


def jnp_cast(v, dtype):
    import jax.numpy as jnp
    return jnp.asarray(v, dtype=dtype)


if __name__ == "__main__":
    import sys
    sys.exit(main())

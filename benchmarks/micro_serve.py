#!/usr/bin/env python
"""Micro-benchmark: serving-tier load generator — QPS + latency
percentiles for the ``roc_tpu/serve`` inference backends.

Builds the SGC serving rig (synthetic graph, frozen Glorot weights —
serving latency is weight-independent), exports BOTH backends through
the real artifact path (``serve/export.py``: resolve → propagation
precompute → AOT prewarm → manifest), then drives a ``Server`` with
two canonical traffic shapes:

1. **closed-loop** — one outstanding query at a time (per client):
   the p50 here is pure request latency, the number the ISSUE's
   "precomputed ≥10× lower p50 than full-graph predict" acceptance is
   measured on;
2. **open-loop Poisson** — arrivals at a fixed rate λ drawn from an
   exponential inter-arrival clock, submitted without waiting for
   completions (the shape real traffic has; p99 under this load shows
   the coalescing queue absorbing bursts instead of head-of-line
   blocking on them).

Reported per backend: p50/p99 request latency (submit→result), QPS
(completed/wall), and the server's microbatch stats.  The headline
speedup row divides full-graph p50 by precomputed p50 — the measured
form of "the fixed-propagation family collapses at serving time".

Closed-loop rows also decompose server-side latency into
``queue_p50_ms`` (admission → dispatch) vs ``device_p50_ms`` (the
microbatch's device wall) from the PR-17 ``ServeResult`` stamps, and
the ``precomputed_noobs`` row re-runs the same load with
``instrument=False`` — the observability-overhead A/B the "registry +
tracing within 5% of instrumentation-off" acceptance reads
(``obs_overhead_pct``).  ``--slo-smoke`` runs ONLY the CI serving
gate: export → cold-load behind a 2-replica Router with declared
SLOs → quiet load-gen → exit 0 iff ``Router.health()`` is green.

The quantized-serving pair (PR 19): the ``precomputed_q8`` row
re-exports the precomputed backend with ``--quantize int8`` and the
``quant_ab`` summary pairs it with the fp32 row — artifact table
bytes (the ≥3× shrink acceptance), p50/p99/QPS, and the export drift
gate's argmax/|Δlogit| measurements (``serve_table_bytes`` /
``serve_quant_drift`` columns).  ``--quant-smoke`` runs ONLY
the PR-19 CI gate: export int8 (drift gate must pass) → cold-load →
load-gen → served answers bit-equal to the gated values, exit 1
otherwise.

Usage: python benchmarks/micro_serve.py [--cpu] [--queries N]
       [--rate QPS|auto] [--out out.json]
The CPU rehearsal artifact lives at benchmarks/micro_serve_cpu.json
(counts and correctness only — nothing in it is a device metric).
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_rig(nodes, degree, feat, classes, hops, seed=0):
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.sgc import build_sgc
    from roc_tpu.train.trainer import TrainConfig
    ds = synthetic_dataset(num_nodes=nodes, avg_degree=degree,
                           in_dim=feat, num_classes=classes, seed=seed)
    model = build_sgc([feat, classes], k=hops, dropout_rate=0.5)
    cfg = TrainConfig(verbose=False, symmetric=True)
    return ds, model, cfg


def _pcts(lat_ms):
    lat = sorted(lat_ms)

    def pct(p):
        return round(lat[min(len(lat) - 1, int(p * len(lat)))], 4)

    return {"p50_ms": pct(0.50), "p99_ms": pct(0.99),
            "mean_ms": round(float(np.mean(lat)), 4)}


def closed_loop(server, ids_seq):
    """One outstanding query at a time; returns latency list + wall
    + the server-side queue/device decomposition (``ServeResult``
    stamps ``queue_ms``/``device_ms`` per request — queue-depth
    pressure vs device wall, the PR-17 latency breakdown;
    ``instrument=False`` servers stamp None and the lists come back
    empty)."""
    lat, queue_ms, device_ms = [], [], []
    t_start = time.perf_counter()
    for ids in ids_seq:
        t0 = time.perf_counter()
        res = server.query(ids)
        lat.append((time.perf_counter() - t0) * 1e3)
        q = getattr(res, "queue_ms", None)
        d = getattr(res, "device_ms", None)
        if q is not None:
            queue_ms.append(q)
        if d is not None:
            device_ms.append(d)
    return lat, time.perf_counter() - t_start, queue_ms, device_ms


def open_loop(server, ids_seq, rate_qps, seed=0):
    """Poisson arrivals at ``rate_qps``; submissions never wait for
    completions, so queueing delay is part of the measured latency
    (the honest open-loop convention)."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / max(rate_qps, 1e-6),
                           size=len(ids_seq))
    done_at = {}

    def _stamp(i):
        # done-callbacks run in the dispatcher thread the moment the
        # future resolves — per-request completion stamps stay honest
        # even when the submitting loop is behind
        def cb(_fut):
            done_at[i] = time.perf_counter()
        return cb

    pending = []
    t_start = time.perf_counter()
    t_next = t_start
    for i, (ids, gap) in enumerate(zip(ids_seq, gaps)):
        t_next += gap
        now = time.perf_counter()
        if t_next > now:
            time.sleep(t_next - now)
        t0 = time.perf_counter()
        fut = server.submit(ids)
        fut.add_done_callback(_stamp(i))
        pending.append((i, t0, fut))
    for _, _, fut in pending:
        fut.result()
    wall = time.perf_counter() - t_start
    # result() can return BEFORE the done-callback ran (set_result
    # wakes waiters first, then invokes callbacks) — give the
    # dispatcher thread a beat to finish stamping
    deadline = time.perf_counter() + 5.0
    while len(done_at) < len(pending) and time.perf_counter() < deadline:
        time.sleep(0.0005)
    t_fallback = time.perf_counter()
    lat = [(done_at.get(i, t_fallback) - t0) * 1e3
           for i, t0, _ in pending]
    return lat, wall


def run_backend(backend, ds, model, cfg, queries, batch, rate,
                art_root, seed=0, max_wait_ms=0.2, instrument=True,
                quant="off"):
    """Export one backend through the real artifact path, then drive
    closed- and open-loop traffic against a cold-loaded server.
    ``instrument=False`` runs the same load with registry recording
    and trace stamping disarmed — the A/B row the observability-
    overhead acceptance (steady-state p50 within 5%) is measured on.
    ``quant='int8'`` exports quantized serving tables (PR 19) — the
    row additionally carries the artifact's table bytes and the
    export drift gate's measurements, the quant:off/quant:int8 A/B
    pair the headline mines."""
    from roc_tpu.serve.export import (build_predictor, export_predictor,
                                      load_predictor)
    from roc_tpu.serve.server import Server
    out_dir = os.path.join(
        art_root, backend + ("" if quant == "off" else f"_{quant}"))
    t0 = time.perf_counter()
    pred = build_predictor(model, ds, cfg, backend=backend,
                           quant=quant)
    manifest = export_predictor(
        pred, out_dir,
        dataset_meta={"V": ds.graph.num_nodes,
                      "E": ds.graph.num_edges})
    export_s = time.perf_counter() - t0
    # the measured server is a COLD load of the artifact — the path a
    # real deployment takes (the export process's jits are not reused)
    t0 = time.perf_counter()
    pred = load_predictor(
        out_dir, dataset=ds if backend == "full" else None)
    warm = pred.warm(name=f"serve_bench_{backend}_{quant}")
    load_s = time.perf_counter() - t0
    rng = np.random.RandomState(seed)
    ids_seq = [rng.randint(0, ds.graph.num_nodes,
                           size=batch).astype(np.int32)
               for _ in range(queries)]
    row = {"backend": backend, "flavor": manifest["flavor"],
           "quant": quant,
           "instrument": bool(instrument),
           "export_s": round(export_s, 2),
           "cold_load_s": round(load_s, 3),
           "warm_hits": warm.get("compile_warm_hits"),
           "cold_compiles": warm.get("compile_cold")}
    # quantized-serving columns (PR 19): the artifact's propagation
    # table bytes (fp32 rows see shrink 1.0) and, for quantized
    # exports, the gate's measured drift — these feed the
    # serve_table_bytes / serve_quant_drift columns
    qb = manifest.get("quant") or {}
    table = qb.get("table") or {}
    if table.get("bytes") is not None:
        row["table_bytes"] = table["bytes"]
        row["table_bytes_fp32"] = table.get("bytes_fp32")
        row["table_shrink"] = table.get("shrink")
    drift = qb.get("drift")
    if drift is not None:
        row["argmax_drift"] = round(
            1.0 - drift["argmax_agreement"], 4)
        row["quant_drift"] = drift["rel_dlogit"]
    with Server(pred, max_wait_ms=max_wait_ms,
                instrument=instrument) as srv:
        # closed loop first — its throughput calibrates 'auto' rate
        lat, wall, qms, dms = closed_loop(srv, ids_seq)
        closed = _pcts(lat)
        closed["qps"] = round(len(lat) / max(wall, 1e-9), 1)
        # queue-delay vs device-time decomposition: where a request's
        # server-side milliseconds actually went
        if qms:
            closed["queue_p50_ms"] = _pcts(qms)["p50_ms"]
        if dms:
            closed["device_p50_ms"] = _pcts(dms)["p50_ms"]
        row["closed"] = closed
        eff_rate = (0.5 * closed["qps"] if rate == "auto"
                    else float(rate))
        lat, wall = open_loop(srv, ids_seq, eff_rate, seed=seed)
        opened = _pcts(lat)
        opened["qps"] = round(len(lat) / max(wall, 1e-9), 1)
        opened["offered_qps"] = round(eff_rate, 1)
        row["open"] = opened
        row["server"] = srv.stats()
    return row


def run_obs_ab(pred, ds, queries, batch, max_wait_ms,
               trials=3, seed=0):
    """Observability-overhead A/B (the 'steady-state p50 within 5%'
    acceptance): alternate instrumented / disarmed closed-loop passes
    over the SAME loaded predictor and compare median-of-trials p50s.
    A single pair is dominated by scheduler jitter at sub-ms request
    latencies (observed ±30% between identical runs); interleaving
    the arms and taking medians cancels the machine drift that a
    sequential pair bakes into one arm."""
    from roc_tpu.serve.server import Server
    rng = np.random.RandomState(seed)
    ids_seq = [rng.randint(0, ds.graph.num_nodes,
                           size=batch).astype(np.int32)
               for _ in range(queries)]
    p50s = {True: [], False: []}
    for trial in range(trials):
        order = (True, False) if trial % 2 == 0 else (False, True)
        for inst in order:
            with Server(pred, max_wait_ms=max_wait_ms,
                        instrument=inst) as srv:
                lat, _, _, _ = closed_loop(srv, ids_seq)
            p50s[inst].append(_pcts(lat)["p50_ms"])
    def _med(vs):
        vs = sorted(vs)
        n = len(vs)
        return vs[n // 2] if n % 2 else 0.5 * (vs[n // 2 - 1]
                                               + vs[n // 2])
    on, off = _med(p50s[True]), _med(p50s[False])
    return {"trials": trials, "queries_per_pass": queries,
            "p50_on_ms": round(on, 4), "p50_off_ms": round(off, 4),
            "p50_on_all": [round(v, 4) for v in p50s[True]],
            "p50_off_all": [round(v, 4) for v in p50s[False]],
            "overhead_pct": round(100.0 * (on - off)
                                  / max(off, 1e-9), 1)}


def run_slo_smoke(ds, model, cfg, art_root, queries=100,
                  n_replicas=2, batch=4, seed=0):
    """The SLO smoke (PR 17 CI gate): export the precomputed backend,
    cold-load it behind a Router with declared objectives, drive a
    quiet load-gen pass, and require ``Router.health()`` green —
    availability 1.0 and every burn rate in-state.  Exit-enforced by
    scripts/test.sh preflight: a serving
    tier that cannot pass a quiet smoke has no business in a round."""
    from roc_tpu.serve.export import build_predictor, export_predictor
    from roc_tpu.serve.router import Router
    out_dir = os.path.join(art_root, "slo_smoke")
    pred = build_predictor(model, ds, cfg, backend="precomputed")
    export_predictor(pred, out_dir,
                     dataset_meta={"V": ds.graph.num_nodes,
                                   "E": ds.graph.num_edges})
    rng = np.random.RandomState(seed)
    ids_seq = [rng.randint(0, ds.graph.num_nodes,
                           size=batch).astype(np.int32)
               for _ in range(queries)]
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("ROC_TPU_FAULT", None)   # a smoke is quiet by definition
    slos = ("availability(ok/requests) >= 0.99 over 30s",
            "latency_p99: p99(request_ms) <= 2000ms over 30s")
    # a genuine breach during the smoke must not litter the caller's
    # cwd with flight records — dumps land next to the artifact
    prev_flight = os.environ.get("ROC_TPU_FLIGHT_DIR")
    os.environ["ROC_TPU_FLIGHT_DIR"] = out_dir
    t0 = time.perf_counter()
    try:
        with Router(out_dir, n_replicas=n_replicas, cpu=True, env=env,
                    default_deadline_ms=30_000.0, slos=slos) as router:
            futs = [router.submit(ids) for ids in ids_seq]
            for f in futs:
                f.result(timeout=60)
            health = router.health()
            stats = router.stats()
    finally:
        if prev_flight is None:
            os.environ.pop("ROC_TPU_FLIGHT_DIR", None)
        else:
            os.environ["ROC_TPU_FLIGHT_DIR"] = prev_flight
    return {"queries": queries, "replicas": n_replicas,
            "ok": bool(health.get("ok")),
            "availability": stats.get("availability"),
            "p99_ms": stats.get("p99_ms"),
            "wall_s": round(time.perf_counter() - t0, 2),
            "health": health}


def run_quant_ab(pred_off, pred_q8, ds, queries, batch,
                 max_wait_ms, trials=4, seed=0):
    """Paired interleaved p50 A/B between the fp32 and int8 loaded
    predictors — the ``run_obs_ab`` precedent: at sub-ms request
    latencies two sequential rows disagree by ±30% on machine drift
    alone, so the 'int8 p50 no worse than fp32' acceptance is
    measured on interleaved arms and median-of-trials, not on the
    independent backend rows."""
    from roc_tpu.serve.server import Server
    rng = np.random.RandomState(seed)
    ids_seq = [rng.randint(0, ds.graph.num_nodes,
                           size=batch).astype(np.int32)
               for _ in range(queries)]
    p50s = {"off": [], "int8": []}
    arms = {"off": pred_off, "int8": pred_q8}
    for trial in range(trials):
        order = (("off", "int8") if trial % 2 == 0
                 else ("int8", "off"))
        for name in order:
            with Server(arms[name], max_wait_ms=max_wait_ms) as srv:
                lat, _, _, _ = closed_loop(srv, ids_seq)
            p50s[name].append(_pcts(lat)["p50_ms"])
    def _med(vs):
        vs = sorted(vs)
        n = len(vs)
        return vs[n // 2] if n % 2 else 0.5 * (vs[n // 2 - 1]
                                               + vs[n // 2])
    off, q8 = _med(p50s["off"]), _med(p50s["int8"])
    return {"trials": trials, "queries_per_pass": queries,
            "p50_off_ms": round(off, 4), "p50_int8_ms": round(q8, 4),
            "p50_off_all": [round(v, 4) for v in p50s["off"]],
            "p50_int8_all": [round(v, 4) for v in p50s["int8"]],
            "delta_pct": round(100.0 * (q8 - off)
                               / max(off, 1e-9), 1)}


def run_quant_smoke(ds, model, cfg, art_root, queries=100,
                    batch=4, mode="int8", seed=0):
    """The quantized-serving smoke (PR 19 CI gate): export the
    precomputed backend at ``mode`` — the export-side drift gate must
    pass (export REFUSES past threshold) — then cold-load the
    artifact, drive a quiet load-gen pass through a Server, and
    require every served answer to match the export-process
    predictor's gated values bit-exactly (the round-trip identity:
    quantize∘dequantize∘quantize is lossless, so a cold load
    reconstructs the same device codes).  Exit-enforced by
    scripts/test.sh preflight: a quantized
    artifact that drifts past the gate, or a cold load that serves
    different values than were gated, never reaches a round."""
    from roc_tpu.serve.export import (build_predictor, export_predictor,
                                      load_predictor)
    from roc_tpu.serve.quant import QuantDriftError
    from roc_tpu.serve.server import Server
    out_dir = os.path.join(art_root, "quant_smoke")
    t_start = time.perf_counter()
    pred = build_predictor(model, ds, cfg, backend="precomputed",
                           quant=mode)
    try:
        manifest = export_predictor(
            pred, out_dir,
            dataset_meta={"V": ds.graph.num_nodes,
                          "E": ds.graph.num_edges})
    except QuantDriftError as e:
        return {"mode": mode, "queries": queries, "ok": False,
                "stage": "export-gate", "error": str(e)}
    qb = manifest["quant"]
    drift = qb["drift"]
    table = qb.get("table") or {}
    rng = np.random.RandomState(seed)
    ids_seq = [rng.randint(0, ds.graph.num_nodes,
                           size=batch).astype(np.int32)
               for _ in range(queries)]
    # reference answers from the export-process predictor — already
    # the gated dequantize∘quantize values the artifact persists
    want = [np.asarray(pred.query(ids)) for ids in ids_seq]
    cold = load_predictor(out_dir)
    wrong = 0
    qmodes = set()
    lat = []
    with Server(cold, max_wait_ms=0.2) as srv:
        for ids, ref in zip(ids_seq, want):
            t0 = time.perf_counter()
            res = srv.query(ids)
            lat.append((time.perf_counter() - t0) * 1e3)
            qmodes.add(getattr(res, "qmode", None))
            if np.abs(np.asarray(res) - ref).max() > 0.0:
                wrong += 1
    ok = (bool(drift.get("ok")) and wrong == 0
          and cold.quant == mode and qmodes == {mode})
    row = {"mode": mode, "queries": queries, "ok": ok,
           "wrong": wrong, "qmode_served": sorted(
               str(m) for m in qmodes),
           "loaded_quant": cold.quant,
           "export_drift": drift,
           "table_bytes": table.get("bytes"),
           "table_shrink": table.get("shrink"),
           "wall_s": round(time.perf_counter() - t_start, 2)}
    row.update(_pcts(lat))
    return row


def run_router_drill(ds, model, cfg, art_root, queries=120,
                     n_replicas=2, kill_batch=4, batch=4,
                     deadline_ms=10_000.0, seed=0):
    """Kill-a-replica load generation (ISSUE 13 acceptance): export
    the precomputed backend, front it with a 2-replica Router, arm
    ``replica_sigkill:<kill_batch>:1`` so replica 1 SIGKILLs itself
    mid-load, and drive queries through the kill.  Every accepted
    request must complete with a correct answer or a typed
    deadline/shed failure — ``wrong`` (answers off by >1e-5 from the
    reference) must be ZERO; failover/hedge counts and the
    availability triple are the row.

    Replicas always run on CPU: this scenario measures AVAILABILITY
    under fault, not device latency (a chip belongs to one process,
    so N replica processes cannot share it — serve/router.py), and
    correctness/failover behavior is platform-independent.  The
    latency rows stay with the single-process backends above."""
    from roc_tpu.serve.errors import ServeOverload, ServeTimeout
    from roc_tpu.serve.export import build_predictor, export_predictor
    from roc_tpu.serve.router import Router
    out_dir = os.path.join(art_root, "router")
    pred = build_predictor(model, ds, cfg, backend="precomputed")
    export_predictor(pred, out_dir,
                     dataset_meta={"V": ds.graph.num_nodes,
                                   "E": ds.graph.num_edges})
    rng = np.random.RandomState(seed)
    ids_seq = [rng.randint(0, ds.graph.num_nodes,
                           size=batch).astype(np.int32)
               for _ in range(queries)]
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["ROC_TPU_FAULT"] = f"replica_sigkill:{kill_batch}:1"
    ok = wrong = timeout = shed = other = 0
    lat = []
    got: dict = {}
    t_start = time.perf_counter()
    with Router(out_dir, n_replicas=n_replicas, cpu=True, env=env,
                default_deadline_ms=deadline_ms) as router:
        futs = []
        for i, ids in enumerate(ids_seq):
            futs.append((i, time.perf_counter(), router.submit(ids)))
            time.sleep(0.002)   # open-ish: keep both replicas busy
        for i, t0, fut in futs:
            try:
                got[i] = np.asarray(fut.result(timeout=60))
                lat.append((time.perf_counter() - t0) * 1e3)
            except ServeTimeout:
                timeout += 1
            except ServeOverload:
                shed += 1
            except Exception:  # noqa: BLE001 - anything else is a bug
                other += 1
        # correctness reference AFTER the load: the SURVIVING replica
        # re-answers every completed request's ids.  Same platform as
        # the drill answers (replicas are CPU even when the parent
        # process sits on a chip — a parent-device reference would
        # compare fp32 across platforms and fail spuriously), and an
        # independent dispatch: cross-request row mixups or torn
        # batches during the failover cannot reproduce in a quiet
        # one-at-a-time re-query
        for i, rows in got.items():
            want = np.asarray(router.query(ids_seq[i],
                                           deadline_ms=60_000.0))
            if np.abs(rows - want).max() > 1e-5:
                wrong += 1
            else:
                ok += 1
        stats = router.stats()
    wall = time.perf_counter() - t_start
    denom = max(queries, 1)
    row = {"queries": queries, "ok": ok, "wrong": wrong,
           "timeout": timeout, "shed": shed, "other_errors": other,
           "failover": stats["n_failover"], "hedge": stats["n_hedge"],
           "replicas_alive": sum(1 for r in stats["replicas"]
                                 if r["alive"]),
           "availability": round(ok / denom, 4),
           "shed_rate": round(shed / denom, 4),
           "error_rate": round((timeout + other + wrong) / denom, 4),
           "wall_s": round(wall, 2)}
    if lat:
        row.update(_pcts(lat))
    return row


def run_shard_capacity(ds, model, cfg, art_root, queries=200,
                       batch=4, n_shards=2, mode="int8", trials=4,
                       seed=0):
    """The sharded-serving capacity proof (ISSUE 20 acceptance): the
    TOTAL propagation table exceeds one replica's enforced byte cap,
    yet the sharded fleet serves every query at availability 1.0 with
    answers bit-exact vs the full-table fleet.  Export ``--shards N``
    at ``mode``, front the slices with ``Router(sharded=True)`` under
    a ``table_budget_bytes`` cap BELOW the full table (a full-table
    replica would refuse to boot), drive load-gen with batches forced
    across the shard boundary, and pair an interleaved p50 A/B
    against a budget-free full-table router over the same artifact.

    The byte acceptance: per-replica bytes ≤ full/N + slack, where
    slack = halo rows + the pad row + the edge-balanced partition's
    imbalance over a perfect V/N split — the gather halo is the ONLY
    structural overhead a slice carries."""
    from roc_tpu.serve.export import build_predictor, export_predictor
    from roc_tpu.serve.quant import table_bytes
    from roc_tpu.serve.router import Router
    out_dir = os.path.join(art_root, "shard_capacity")
    t_start = time.perf_counter()
    pred = build_predictor(model, ds, cfg, backend="precomputed",
                           quant=mode)
    manifest = export_predictor(
        pred, out_dir,
        dataset_meta={"V": ds.graph.num_nodes,
                      "E": ds.graph.num_edges},
        shards=n_shards)
    sb = manifest["shards"]
    shard_bytes = int(sb["bytes_per_replica"])
    full_bytes = int(sb["bytes_full"])
    V, F = ds.graph.num_nodes, int(pred.cache.table.shape[1])
    # the cap: midway between one slice and the full table — a
    # full-table replica CANNOT boot under it, a slice fits
    budget = (shard_bytes + full_bytes) // 2
    slack = int(table_bytes(
        (int(sb["halo"]) + 1 + (int(sb["rows_padded"]) - V // n_shards),
         F), mode))
    bytes_ok = (shard_bytes <= budget < full_bytes
                and shard_bytes <= full_bytes // n_shards + slack)
    rng = np.random.RandomState(seed)
    ids_seq = [rng.randint(0, V, size=batch).astype(np.int32)
               for _ in range(queries)]
    # force a third of the batches across the first shard boundary —
    # a capacity row that never gathers proves nothing
    b = int(sb["plan"][0][1])
    for i in range(0, len(ids_seq), 3):
        ids_seq[i][:2] = (b - 1, b)
    want = [np.asarray(pred.query(ids)) for ids in ids_seq]
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("ROC_TPU_FAULT", None)
    wrong = 0
    p50s = {"full": [], "sharded": []}
    with Router(out_dir, n_replicas=n_shards, cpu=True, env=env,
                default_deadline_ms=60_000.0) as r_full, \
         Router(out_dir, n_replicas=n_shards, cpu=True, env=env,
                sharded=True, table_budget_bytes=budget,
                default_deadline_ms=60_000.0) as r_shard:
        # correctness + availability on the sharded arm first
        futs = [r_shard.submit(ids) for ids in ids_seq]
        for f, ref in zip(futs, want):
            if np.abs(np.asarray(f.result(timeout=120))
                      - ref).max() > 0.0:
                wrong += 1
        shard_stats = r_shard.stats()
        # paired interleaved p50 A/B (run_obs_ab precedent): both
        # routers warm, alternate arm order per trial
        arms = {"full": r_full, "sharded": r_shard}
        for trial in range(trials):
            order = (("full", "sharded") if trial % 2 == 0
                     else ("sharded", "full"))
            for name in order:
                lat = []
                for ids in ids_seq:
                    t0 = time.perf_counter()
                    arms[name].query(ids, deadline_ms=60_000.0)
                    lat.append((time.perf_counter() - t0) * 1e3)
                p50s[name].append(_pcts(lat)["p50_ms"])

    def _med(vs):
        vs = sorted(vs)
        n = len(vs)
        return vs[n // 2] if n % 2 else 0.5 * (vs[n // 2 - 1]
                                               + vs[n // 2])
    p50_full, p50_shard = _med(p50s["full"]), _med(p50s["sharded"])
    avail = shard_stats.get("availability")
    ok = bool(bytes_ok and wrong == 0 and avail == 1.0)
    return {"mode": mode, "n_shards": n_shards, "queries": queries,
            "ok": ok, "wrong": wrong, "availability": avail,
            "table_budget_bytes": budget,
            "serve_shard_table_bytes": shard_bytes,
            "full_table_bytes": full_bytes,
            "bytes_slack": slack, "bytes_ok": bytes_ok,
            "halo": int(sb["halo"]),
            "serve_gather_p50_ms": shard_stats.get("gather_p50_ms"),
            "p50_full_ms": round(p50_full, 4),
            "p50_sharded_ms": round(p50_shard, 4),
            "p50_full_all": [round(v, 4) for v in p50s["full"]],
            "p50_sharded_all": [round(v, 4)
                                for v in p50s["sharded"]],
            "delta_pct": round(100.0 * (p50_shard - p50_full)
                               / max(p50_full, 1e-9), 1),
            "wall_s": round(time.perf_counter() - t_start, 2)}


def run_shard_smoke(ds, model, cfg, art_root, queries=100,
                    batch=4, n_shards=2, mode="int8", seed=0):
    """The sharded-serving smoke (ISSUE 20 CI gate): export
    ``--shards 2``, cold-load ONE slice directly (the zero-new-
    compiles parity check inside ``load_predictor`` must pass), then
    front the slices with a 2-replica sharded Router under a byte cap
    below the full table and drive a load-gen pass whose batches
    straddle the shard boundary.  Every answer must match the
    export-process predictor bit-exactly.  Exit-enforced by
    scripts/test.sh preflight: a fleet that
    cannot gather across its own shards never reaches a round."""
    from roc_tpu.serve.export import (build_predictor, export_predictor,
                                      load_predictor)
    from roc_tpu.serve.router import Router
    out_dir = os.path.join(art_root, "shard_smoke")
    t_start = time.perf_counter()
    pred = build_predictor(model, ds, cfg, backend="precomputed",
                           quant=mode)
    manifest = export_predictor(
        pred, out_dir,
        dataset_meta={"V": ds.graph.num_nodes,
                      "E": ds.graph.num_edges},
        shards=n_shards)
    sb = manifest["shards"]
    shard_bytes = int(sb["bytes_per_replica"])
    full_bytes = int(sb["bytes_full"])
    budget = (shard_bytes + full_bytes) // 2
    # cold slice load: program-key parity vs the manifest's shard warm
    # set is asserted inside load_predictor (raises on mismatch), and
    # a slice answers its OWNED ids bit-exactly with no gather path
    cold0 = load_predictor(out_dir, shard=0)
    lo0, hi0 = cold0.shard
    own_ids = np.arange(lo0, min(hi0, lo0 + batch), dtype=np.int32)
    cold_wrong = int(np.abs(np.asarray(cold0.query(own_ids))
                            - np.asarray(pred.query(own_ids))
                            ).max() > 0.0)
    rng = np.random.RandomState(seed)
    V = ds.graph.num_nodes
    ids_seq = [rng.randint(0, V, size=batch).astype(np.int32)
               for _ in range(queries)]
    b = int(sb["plan"][0][1])
    for i in range(0, len(ids_seq), 3):
        ids_seq[i][:2] = (b - 1, b)   # cross-shard ids, every 3rd
    want = [np.asarray(pred.query(ids)) for ids in ids_seq]
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("ROC_TPU_FAULT", None)   # a smoke is quiet by definition
    wrong = 0
    with Router(out_dir, n_replicas=n_shards, cpu=True, env=env,
                sharded=True, table_budget_bytes=budget,
                default_deadline_ms=60_000.0) as router:
        futs = [router.submit(ids) for ids in ids_seq]
        for f, ref in zip(futs, want):
            if np.abs(np.asarray(f.result(timeout=120))
                      - ref).max() > 0.0:
                wrong += 1
        stats = router.stats()
    avail = stats.get("availability")
    ok = bool(wrong == 0 and cold_wrong == 0 and avail == 1.0
              and shard_bytes <= budget < full_bytes)
    return {"mode": mode, "n_shards": n_shards, "queries": queries,
            "ok": ok, "wrong": wrong, "cold_slice_wrong": cold_wrong,
            "availability": avail,
            "table_budget_bytes": budget,
            "shard_table_bytes": shard_bytes,
            "full_table_bytes": full_bytes,
            "gather_p50_ms": stats.get("gather_p50_ms"),
            "wall_s": round(time.perf_counter() - t_start, 2)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--feat", type=int, default=128)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--hops", type=int, default=2)
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4,
                    help="node ids per query (the per-user request "
                         "size; microbatching coalesces across them)")
    ap.add_argument("--rate", default="auto",
                    help="open-loop Poisson arrival rate in QPS "
                         "('auto' = half the measured closed-loop "
                         "throughput)")
    ap.add_argument("--backends", default="precomputed,full")
    ap.add_argument("--max-wait-ms", type=float, default=0.2)
    ap.add_argument("--drill", action="store_true",
                    help="also run the kill-a-replica router drill "
                         "(2 CPU replicas, replica 1 SIGKILLed "
                         "mid-load; availability/failover row)")
    ap.add_argument("--slo-smoke", action="store_true",
                    help="run ONLY the SLO smoke: export → cold-load "
                         "behind a 2-replica Router with declared "
                         "objectives → quiet load-gen → require "
                         "health green (exit 1 otherwise) — the CI "
                         "serving-tier gate")
    ap.add_argument("--quant-smoke", action="store_true",
                    help="run ONLY the quantized-serving smoke: "
                         "export int8 (drift gate must pass) → "
                         "cold-load → load-gen → served answers must "
                         "match the gated values bit-exactly (exit 1 "
                         "otherwise) — the PR-19 CI gate")
    ap.add_argument("--shard-smoke", action="store_true",
                    help="run ONLY the sharded-serving smoke: export "
                         "--shards 2 → cold-load one slice → sharded "
                         "Router under a byte cap below the full "
                         "table → load-gen with cross-shard ids, "
                         "bit-exact answers required (exit 1 "
                         "otherwise) — the PR-20 CI gate")
    ap.add_argument("--no-shard-ab", action="store_true",
                    help="skip the sharded-capacity row (2-shard "
                         "int8 export behind a byte-capped sharded "
                         "Router vs a full-table fleet; the "
                         "shard-bytes/gather acceptance)")
    ap.add_argument("--no-quant-ab", action="store_true",
                    help="skip the quant:int8 A/B row (precomputed "
                         "backend re-exported with --quantize int8; "
                         "the table-bytes/drift acceptance)")
    ap.add_argument("--no-obs-ab", action="store_true",
                    help="skip the instrumentation-off A/B row "
                         "(precomputed backend re-run with "
                         "instrument=False; the observability-"
                         "overhead acceptance)")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write the result JSON here (e.g. "
                         "benchmarks/micro_serve_cpu.json)")
    args = ap.parse_args(argv)
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from roc_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache(min_compile_secs=0.0)
    dev = jax.devices()[0]
    ds, model, cfg = build_rig(args.nodes, args.degree, args.feat,
                               args.classes, args.hops)
    if args.quant_smoke:
        from roc_tpu.models.builder import Model
        with tempfile.TemporaryDirectory(prefix="roc_quant_") as art:
            row = run_quant_smoke(
                ds, Model.from_spec(model.to_spec()), cfg, art,
                queries=args.queries, batch=args.batch)
        drift = row.get("export_drift") or {}
        print(f"# quant smoke: {'GREEN' if row['ok'] else 'RED'} "
              f"({row['queries']} queries, mode {row['mode']}, "
              f"rel drift {drift.get('rel_dlogit')}, "
              f"shrink {row.get('table_shrink')}x, "
              f"{row.get('wrong', '?')} served mismatches)",
              file=sys.stderr)
        print(json.dumps(row))
        return 0 if row["ok"] else 1
    if args.shard_smoke:
        from roc_tpu.models.builder import Model
        with tempfile.TemporaryDirectory(prefix="roc_shard_") as art:
            row = run_shard_smoke(
                ds, Model.from_spec(model.to_spec()), cfg, art,
                queries=args.queries, batch=args.batch)
        print(f"# shard smoke: {'GREEN' if row['ok'] else 'RED'} "
              f"({row['queries']} queries over {row['n_shards']} "
              f"shards, {row['wrong']} wrong, availability "
              f"{row['availability']}, slice "
              f"{row['shard_table_bytes']} B ≤ cap "
              f"{row['table_budget_bytes']} B < full "
              f"{row['full_table_bytes']} B, gather p50 "
              f"{row['gather_p50_ms']} ms)", file=sys.stderr)
        print(json.dumps(row))
        return 0 if row["ok"] else 1
    if args.slo_smoke:
        from roc_tpu.models.builder import Model
        with tempfile.TemporaryDirectory(prefix="roc_slo_") as art:
            row = run_slo_smoke(
                ds, Model.from_spec(model.to_spec()), cfg, art,
                queries=args.queries, batch=args.batch)
        print(f"# slo smoke: {'GREEN' if row['ok'] else 'RED'} "
              f"({row['queries']} queries, availability "
              f"{row['availability']}, p99 {row['p99_ms']} ms)",
              file=sys.stderr)
        print(json.dumps(row))
        return 0 if row["ok"] else 1
    out = {"device": f"{dev.platform} {dev.device_kind}",
           "config": {"V": ds.graph.num_nodes,
                      "E": ds.graph.num_edges, "F": args.feat,
                      "C": args.classes, "k": args.hops,
                      "queries": args.queries, "batch": args.batch,
                      "max_wait_ms": args.max_wait_ms},
           "backends": {}}
    with tempfile.TemporaryDirectory(prefix="roc_serve_") as art:
        for backend in [b.strip()
                        for b in args.backends.split(",") if b.strip()]:
            from roc_tpu.models.builder import Model
            row = run_backend(
                backend, ds, Model.from_spec(model.to_spec()), cfg,
                args.queries, args.batch, args.rate, art)
            out["backends"][backend] = row
            print(f"# {backend}: closed p50 "
                  f"{row['closed']['p50_ms']} ms p99 "
                  f"{row['closed']['p99_ms']} ms "
                  f"{row['closed']['qps']} qps (queue p50 "
                  f"{row['closed'].get('queue_p50_ms')} / device p50 "
                  f"{row['closed'].get('device_p50_ms')} ms) | open "
                  f"p50 {row['open']['p50_ms']} ms p99 "
                  f"{row['open']['p99_ms']} ms", file=sys.stderr)
        if "precomputed" in out["backends"] and not args.no_quant_ab:
            # the quantized-serving A/B (PR 19): same backend, same
            # load, tables + params exported at int8 — the paired
            # quant:off/quant:int8 rows the table-bytes/drift
            # acceptance reads
            from roc_tpu.models.builder import Model
            row = run_backend(
                "precomputed", ds, Model.from_spec(model.to_spec()),
                cfg, args.queries, args.batch, args.rate, art,
                quant="int8")
            out["backends"]["precomputed_q8"] = row
            pre = out["backends"]["precomputed"]
            out["quant_ab"] = {
                "table_bytes_off": pre.get("table_bytes"),
                "table_bytes_int8": row.get("table_bytes"),
                "table_shrink": row.get("table_shrink"),
                "p50_off_ms": pre["closed"]["p50_ms"],
                "p50_int8_ms": row["closed"]["p50_ms"],
                "p99_off_ms": pre["closed"]["p99_ms"],
                "p99_int8_ms": row["closed"]["p99_ms"],
                "qps_off": pre["closed"]["qps"],
                "qps_int8": row["closed"]["qps"],
                "argmax_drift": row.get("argmax_drift"),
                "quant_drift": row.get("quant_drift")}
            # the headline p50 comparison comes from a PAIRED
            # interleaved A/B over the two cold-loaded artifacts —
            # the sequential rows above drift ±30% at sub-ms p50s
            from roc_tpu.serve.export import load_predictor
            p_off = load_predictor(os.path.join(art, "precomputed"))
            p_off.warm(name="serve_quant_ab_off")
            p_q8 = load_predictor(
                os.path.join(art, "precomputed_int8"))
            p_q8.warm(name="serve_quant_ab_int8")
            paired = run_quant_ab(p_off, p_q8, ds, args.queries,
                                  args.batch, args.max_wait_ms)
            out["quant_ab"]["paired"] = paired
            print(f"# quant A/B: table {pre.get('table_bytes')} B "
                  f"fp32 → {row.get('table_bytes')} B int8 "
                  f"({row.get('table_shrink')}x), paired p50 "
                  f"{paired['p50_off_ms']} → "
                  f"{paired['p50_int8_ms']} ms "
                  f"({paired['delta_pct']:+.1f}%), argmax drift "
                  f"{row.get('argmax_drift')}", file=sys.stderr)
        if "precomputed" in out["backends"] and not args.no_obs_ab:
            # the observability-overhead A/B: same backend, same
            # load, registry + trace stamping disarmed
            from roc_tpu.models.builder import Model
            row = run_backend(
                "precomputed", ds, Model.from_spec(model.to_spec()),
                cfg, args.queries, args.batch, args.rate,
                os.path.join(art, "noobs"), instrument=False)
            out["backends"]["precomputed_noobs"] = row
            # the headline overhead number comes from a PAIRED
            # interleaved A/B over one loaded predictor, not the two
            # independent rows above — at sub-ms p50s the sequential
            # rows disagree by ±30% on machine drift alone
            from roc_tpu.serve.export import load_predictor
            pred = load_predictor(os.path.join(art, "precomputed"))
            pred.warm(name="serve_obs_ab")
            ab = run_obs_ab(pred, ds, args.queries, args.batch,
                            args.max_wait_ms)
            out["obs_ab"] = ab
            out["obs_overhead_pct"] = ab["overhead_pct"]
            print(f"# obs overhead (paired A/B, median of "
                  f"{ab['trials']}): instrumented p50 "
                  f"{ab['p50_on_ms']} ms vs off {ab['p50_off_ms']} ms "
                  f"({ab['overhead_pct']:+.1f}%)", file=sys.stderr)
        if not args.no_shard_ab:
            # the sharded-capacity row (PR 20): total table above one
            # replica's byte cap, served sharded at availability 1.0
            # bit-exact, paired p50 vs the full-table fleet
            from roc_tpu.models.builder import Model
            row = run_shard_capacity(
                ds, Model.from_spec(model.to_spec()), cfg, art,
                queries=min(args.queries, 60), batch=args.batch)
            out["shard_capacity"] = row
            print(f"# shard capacity: {'OK' if row['ok'] else 'RED'} "
                  f"slice {row['serve_shard_table_bytes']} B ≤ cap "
                  f"{row['table_budget_bytes']} B < full "
                  f"{row['full_table_bytes']} B, {row['wrong']} "
                  f"wrong, availability {row['availability']}, "
                  f"paired p50 {row['p50_full_ms']} → "
                  f"{row['p50_sharded_ms']} ms "
                  f"({row['delta_pct']:+.1f}%), gather p50 "
                  f"{row['serve_gather_p50_ms']} ms",
                  file=sys.stderr)
        if args.drill:
            from roc_tpu.models.builder import Model
            row = run_router_drill(
                ds, Model.from_spec(model.to_spec()), cfg, art,
                batch=args.batch)
            out["router_drill"] = row
            print(f"# router drill: {row['ok']}/{row['queries']} ok, "
                  f"{row['wrong']} wrong, {row['timeout']} timeout, "
                  f"{row['failover']} failed over "
                  f"(availability {row['availability']})",
                  file=sys.stderr)
    pre = out["backends"].get("precomputed")
    full = out["backends"].get("full")
    if pre and full:
        out["speedup_p50"] = round(
            full["closed"]["p50_ms"] / max(pre["closed"]["p50_ms"],
                                           1e-9), 1)
        print(f"# precomputed vs full-graph p50 speedup: "
              f"{out['speedup_p50']}x", file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"# wrote {args.out}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

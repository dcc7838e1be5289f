#!/usr/bin/env python
"""Full-epoch race on the community substrate: does the block-dense
aggregation win survive end-to-end?

A micro race of sectioned against bdense measures ONE
aggregation; an epoch is 2 forward + 2 backward aggregations plus the
dense stack, so this script runs the headline GCN workload
(602-256-41, dropout 0.5, Adam — example_run.sh:1 semantics) through
complete training epochs per impl on the SAME reordered community
graph.  The aggregation is ~98% of the epoch (BASELINE.md), so the
micro win should transfer near-1:1; this record is the proof.

    python benchmarks/epoch_community.py            # planted:16384+lpa

Records to measured_baselines.json:
full_graph_gcn_epoch_time_community when run on the chip.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))
_BASELINES = os.path.join(HERE, "measured_baselines.json")
METRIC = "full_graph_gcn_epoch_time_community"


def main() -> int:
    from _substrates import GRAPH_SPEC_HELP
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=232_965)
    ap.add_argument("--edges", type=int, default=114_848_857)
    ap.add_argument("--layers", default="602-256-41")
    ap.add_argument("--dtype", default="mixed",
                    choices=["float32", "bfloat16", "mixed"])
    ap.add_argument("--impls", default="sectioned,bdense")
    ap.add_argument("--epochs", type=int, default=10,
                    help="timed epochs per impl (median recorded)")
    ap.add_argument("--graph", default="planted:16384",
                    help=GRAPH_SPEC_HELP)
    ap.add_argument("--reorder", default="lpa",
                    choices=["none", "bfs", "lpa"])
    ap.add_argument("--min-fill", type=int, default=64)
    ap.add_argument("--a-budget", type=int, default=2 << 30,
                    help="bdense A-table byte cap (0 = uncapped)")
    ap.add_argument("--bdense-group", type=int, default=1,
                    help="dense blocks reduced per output-tile update "
                         "(pad_plan_groups; cuts output RMW traffic)")
    ap.add_argument("--cpu", action="store_true",
                    help="CPU rehearsal; result NOT recorded")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from _substrates import graph_from_spec, reorder_graph
    from roc_tpu.core.graph import Dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import (TrainConfig, Trainer,
                                       resolve_dtypes)
    from roc_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    layers = [int(x) for x in args.layers.split("-")]

    t0 = time.time()
    graph = graph_from_spec(args.graph, args.nodes, args.edges)
    gen_s = time.time() - t0
    graph, reorder_s = reorder_graph(
        graph, args.reorder,
        cache_key=f"{args.graph}_{args.nodes}_{args.edges}")
    print(f"# {dev.platform} {dev.device_kind}: "
          f"V={graph.num_nodes:,} E={graph.num_edges:,} "
          f"gen {gen_s:.0f}s, {args.reorder} reorder {reorder_s:.0f}s",
          file=sys.stderr)

    # random labels/split: epoch TIME is
    # independent of label identity (convergence is gated separately by
    # convergence_scale.py)
    rng = np.random.RandomState(1)
    ds = Dataset(
        graph=graph,
        features=rng.rand(args.nodes, layers[0]).astype(np.float32),
        labels=rng.randint(0, layers[-1],
                           size=args.nodes).astype(np.int32),
        mask=rng.choice([1, 2, 3], size=args.nodes,
                        p=[0.66, 0.10, 0.24]).astype(np.int32),
        num_classes=layers[-1],
        name=f"community-{args.graph}+{args.reorder}")

    dtype, compute_dtype = resolve_dtypes(args.dtype)
    rows = {}
    for spec in args.impls.split(","):
        # 'IMPL+fuse' races the fused-normalization path (table-baked
        # D^-1/2 + fused epilogue) against the bare 'IMPL' row — the
        # epoch-level form of a chain-against-fused micro race
        impl, _, fuse_tag = spec.partition("+")
        if fuse_tag not in ("", "fuse"):
            print(f"# unknown impl spec {spec!r} (IMPL or IMPL+fuse)",
                  file=sys.stderr)
            continue
        cfg = TrainConfig(learning_rate=0.01, weight_decay=1e-4,
                          decay_rate=0.97, decay_steps=100,
                          aggr_impl=impl, dtype=dtype,
                          compute_dtype=compute_dtype,
                          aggr_fuse="on" if fuse_tag else "off",
                          bdense_min_fill=args.min_fill,
                          bdense_a_budget=args.a_budget or None,
                          bdense_group=args.bdense_group,
                          verbose=False, eval_every=1 << 30,
                          symmetric=True)
        t0 = time.time()
        trainer = Trainer(build_gcn(layers, dropout_rate=0.5), ds, cfg)
        trainer.train(epochs=2)   # compile lap + warmup
        trainer.sync()
        compile_s = time.time() - t0
        times = []
        for _ in range(args.epochs):
            t0 = time.time()
            trainer.train(epochs=1)
            trainer.sync()
            times.append((time.time() - t0) * 1000.0)
        row = {"compile_s": round(compile_s, 1),
               "epoch_ms": round(float(np.median(times)), 2),
               "epoch_ms_all": [round(t, 1) for t in times]}
        if fuse_tag:
            row["aggr_fuse"] = "on"
        if impl == "bdense":
            row["min_fill"] = args.min_fill
            row["a_budget"] = args.a_budget
            if args.bdense_group > 1:
                row["bdense_group"] = args.bdense_group
        rows[spec] = row
        print(f"# {spec}: epoch {row['epoch_ms']} ms "
              f"(compile {compile_s:.0f}s)", file=sys.stderr)
        del trainer

    line = {"metric": METRIC,
            "V": args.nodes, "E": int(graph.num_edges),
            "layers": args.layers, "dtype": args.dtype,
            "graph": args.graph, "reorder": args.reorder,
            "gen_s": round(gen_s, 1), "reorder_s": round(reorder_s, 1),
            "platform": dev.platform, "device_kind": dev.device_kind,
            "impls": rows,
            "labels": "synthetic_random (timing only; convergence is "
                      "convergence_scale.py's gate)"}
    if not args.cpu and dev.platform == "tpu":
        try:
            with open(_BASELINES) as f:
                db = json.load(f)
        except (OSError, ValueError):
            db = {}
        rec = dict(line)
        rec["recorded"] = time.strftime("%Y-%m-%dT%H:%M:%S%z")
        rec["provenance"] = ("benchmarks/epoch_community.py --graph "
                             f"{args.graph} --reorder {args.reorder} "
                             f"--dtype {args.dtype} --min-fill "
                             f"{args.min_fill}")
        db[METRIC] = rec
        tmp = _BASELINES + ".tmp"
        with open(tmp, "w") as f:
            json.dump(db, f, indent=1, sort_keys=True)
        os.replace(tmp, _BASELINES)
        print(f"# recorded -> {_BASELINES}", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Micro-benchmark: greedy edge sweep vs cost-balanced partitioning.

The SPMD layer pads every shard to the MAX shard's quantized shapes,
so the straggler's padded program gates every step and every ring hop
— the split IS the cost.  Three probes per (substrate, P):

1. **split race** (host): greedy (``edge_balanced_bounds``, the
   reference ``gnn.cc:806-829`` sweep) vs cost
   (``costmodel.cost_balanced_bounds`` minimax search) — modeled
   max-shard cost, padded part shapes, edge imbalance, split wall ms.
2. **max-shard step race** (device): the straggler's padded
   aggregation program under each split — a gather + segment-sum over
   ``part_edges`` padded edges into ``part_nodes`` rows, i.e. exactly
   the per-device shape the distributed step compiles.  The cost split
   must reduce this measured time, not just the model's number.
3. **distributed epoch race** (when the backend has >= P devices):
   short GCN training runs with ``partition='greedy'`` vs ``'cost'``,
   median steady epoch_ms.

Substrates: ``zipf[:A]`` power-law in-degrees (the acceptance
substrate — Zipf hubs are the edge-balanced sweep's worst case) and
the Reddit-shaped ``planted`` community graph.

Usage: python benchmarks/micro_partition.py [--cpu] [--out out.json]
The CPU rehearsal artifact lives at benchmarks/micro_partition_cpu.json;
on a chip: not measured.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from _substrates import GRAPH_SPEC_HELP, graph_from_spec  # noqa: E402


def bench(fn, iters=10):
    """Median wall ms with the fetch-based barrier."""
    import jax.numpy as jnp
    out = fn()
    float(jnp.sum(out))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        float(jnp.sum(out))
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def split_row(g, parts, method, weights, nm, em):
    """Host-side split + modeled stats for one method."""
    from roc_tpu.core.costmodel import bounds_max_cost
    from roc_tpu.core.partition import partition_bounds, partition_plan
    t0 = time.perf_counter()
    bounds = partition_bounds(g.row_ptr, parts, method=method,
                              node_multiple=nm, edge_multiple=em,
                              cost_weights=weights)
    split_ms = (time.perf_counter() - t0) * 1e3
    plan = partition_plan(g.row_ptr, parts, node_multiple=nm,
                          edge_multiple=em, method=method,
                          cost_weights=weights)
    re = np.asarray(plan.real_edges, dtype=np.float64)
    return plan, {
        "split_ms": round(split_ms, 2),
        "modeled_max_cost": round(float(bounds_max_cost(
            g.row_ptr, bounds, weights[0], weights[1], nm, em)), 7),
        "part_nodes": int(plan.part_nodes),
        "part_edges": int(plan.part_edges),
        "max_real_edges": int(re.max()),
        "edge_imbalance": round(float(re.max() / max(re.mean(), 1)),
                                4),
    }


def shard_step_ms(g, plan, F, iters):
    """Measured straggler step: the padded per-device aggregation
    program this split compiles — [part_edges] gather + sorted
    segment-sum into [part_nodes] rows (dummy source = the appended
    zero row, exactly the trainers' convention)."""
    import jax
    import jax.numpy as jnp
    from roc_tpu.core.partition import materialize_plan
    from roc_tpu.ops.aggregate import aggregate
    pg = materialize_plan(g, plan)
    p = int(np.argmax(pg.real_edges))
    src = jnp.asarray(pg.part_col_idx[p])          # [part_edges]
    dst = jnp.asarray(np.repeat(
        np.arange(pg.part_nodes, dtype=np.int32),
        np.diff(pg.part_row_ptr[p])))
    x = np.random.RandomState(0).rand(
        g.num_nodes + 1, F).astype(np.float32)
    x[-1] = 0
    xj = jnp.asarray(x)
    f = jax.jit(lambda xx: aggregate(xx, src, dst, pg.part_nodes,
                                     impl="segment"))
    return bench(lambda: f(xj), iters)


def epoch_race(g, parts, epochs):
    """Distributed GCN epochs per partition method (>= P devices)."""
    import jax
    if len(jax.devices()) < parts:
        return {"skipped": f"{len(jax.devices())} device(s)"}
    from roc_tpu.core.graph import MASK_NONE, Dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig
    rng = np.random.RandomState(1)
    V, F, C = g.num_nodes, 64, 8
    ds = Dataset(graph=g,
                 features=rng.rand(V, F).astype(np.float32),
                 labels=rng.randint(0, C, size=V).astype(np.int32),
                 mask=np.full(V, MASK_NONE, dtype=np.int32),
                 num_classes=C, name="micro_partition")
    ds.mask[rng.rand(V) < 0.5] = 1
    rows = {}
    for method in ("greedy", "cost"):
        cfg = TrainConfig(verbose=False, symmetric=True,
                          dropout_rate=0.0, partition=method,
                          eval_every=1 << 30, epochs=epochs)
        tr = DistributedTrainer(build_gcn([F, 32, C],
                                          dropout_rate=0.0),
                                ds, parts, cfg)
        tr.train(epochs=2)   # compile + warmup
        tr.sync()
        times = []
        for _ in range(epochs):
            t0 = time.perf_counter()
            tr.train(epochs=1)
            tr.sync()
            times.append((time.perf_counter() - t0) * 1e3)
        rows[method] = {"epoch_ms": round(float(np.median(times)), 2),
                        "part_edges": int(tr.pg.part_edges)}
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=131_072)
    ap.add_argument("--edges", type=int, default=2_621_440)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--graphs", type=str,
                    default="zipf:1.2,planted:16384",
                    help=f"comma list of substrates: {GRAPH_SPEC_HELP}")
    ap.add_argument("--parts", type=str, default="4,8",
                    help="comma list of shard counts")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--edge-multiple", type=int, default=512)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    print(f"# device={dev.platform} {dev.device_kind} "
          f"V={args.nodes} E={args.edges}", file=sys.stderr)

    # cold-start weights: the edge-balance prior the trainers use
    from roc_tpu.core.costmodel import PartitionCostModel
    weights = PartitionCostModel().search_weights()
    nm, em = 8, args.edge_multiple

    result = {"device": f"{dev.platform} {dev.device_kind}",
              "config": vars(args) | {"weights": list(weights)},
              "races": {}}
    wins = []
    for spec in args.graphs.split(","):
        g = graph_from_spec(spec, args.nodes, args.edges)
        for parts in (int(p) for p in args.parts.split(",")):
            row = {}
            plans = {}
            for method in ("greedy", "cost"):
                plans[method], row[method] = split_row(
                    g, parts, method, weights, nm, em)
                row[method]["shard_step_ms"] = round(shard_step_ms(
                    g, plans[method], args.dim, args.iters), 3)
            row["epochs"] = epoch_race(g, parts, args.epochs)
            win = {
                "modeled_reduced": bool(
                    row["cost"]["modeled_max_cost"]
                    <= row["greedy"]["modeled_max_cost"]),
                "measured_reduced": bool(
                    row["cost"]["shard_step_ms"]
                    <= row["greedy"]["shard_step_ms"]),
                "part_edges_ratio": round(
                    row["cost"]["part_edges"]
                    / max(row["greedy"]["part_edges"], 1), 4),
            }
            row["win"] = win
            wins.append(win)
            result["races"][f"{spec}/P{parts}"] = row
            print(f"# {spec} P={parts}: part_edges "
                  f"{row['greedy']['part_edges']} -> "
                  f"{row['cost']['part_edges']} "
                  f"({win['part_edges_ratio']:.2f}x), shard step "
                  f"{row['greedy']['shard_step_ms']} -> "
                  f"{row['cost']['shard_step_ms']} ms",
                  file=sys.stderr)
    result["win"] = {
        "modeled_reduced_all": bool(all(w["modeled_reduced"]
                                        for w in wins)),
        "measured_reduced_any": bool(any(w["measured_reduced"]
                                         for w in wins)),
    }
    line = json.dumps(result, indent=1)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

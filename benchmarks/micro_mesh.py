#!/usr/bin/env python
"""Micro-benchmark: 1-D all-parts mesh vs the (parts, model) 2-D mesh.

One wide GCN (F >= 256 — the regime where weight matrices and Adam
moments stop being rounding errors next to the graph blocks) races
every ``candidate_mesh_shapes`` factorization of the SAME device set:

1. **epoch race** — median steady epoch wall ms per shape.  The parts
   axis is the partition count, so each shape retrains with its own
   split; the device set is constant, so the numbers are directly
   comparable.
2. **at-rest HBM race** — measured bytes of params + Adam moments
   resident on device 0 under each shape (the replication the
   auditor's ledger models, read off the live shardings), plus the
   backend's ``memory_stats`` peak when it exposes one (TPU; CPU
   rehearsals report null).

The degenerate all-parts shape (Px1) IS today's 1-D mesh and anchors
the race; ``mesh_epoch_ratio`` = best-2-D / 1-D epoch time (< 1.0
means the model axis pays for itself on this substrate).

Usage: python benchmarks/micro_mesh.py [--cpu] [--out out.json]
The CPU rehearsal artifact lives at benchmarks/micro_mesh_cpu.json
(8 virtual host devices); on a chip: not measured.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_wide_dataset(nodes, degree, dim, classes, seed=0):
    from roc_tpu.core.graph import MASK_NONE, Dataset, random_csr
    g = random_csr(nodes, degree * nodes, seed=seed)
    rng = np.random.RandomState(seed + 1)
    ds = Dataset(graph=g,
                 features=rng.rand(nodes, dim).astype(np.float32),
                 labels=rng.randint(0, classes,
                                    size=nodes).astype(np.int32),
                 mask=np.full(nodes, MASK_NONE, dtype=np.int32),
                 num_classes=classes, name="micro_mesh")
    ds.mask[rng.rand(nodes) < 0.5] = 1
    return ds


def state_bytes_on_device(tr, device) -> int:
    """Measured at-rest bytes of params + Adam moments on ONE device —
    the live counterpart of the auditor's params/opt_state ledger rows
    (model-sharded leaves put only their slice here)."""
    import jax
    total = 0
    for tree in (tr.params, tr.opt_state.m, tr.opt_state.v):
        for leaf in jax.tree_util.tree_leaves(tree):
            for sh in leaf.addressable_shards:
                if sh.device == device:
                    total += int(sh.data.nbytes)
    return total


def mesh_row(ds, parts, model, hidden, epochs, warmup=2):
    """Train the wide GCN on one (parts, model) shape: median steady
    epoch ms + the at-rest state bytes race."""
    import jax
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig
    cfg = TrainConfig(verbose=False, symmetric=True, dropout_rate=0.0,
                      eval_every=1 << 30,
                      mesh="auto" if model == 1 else f"{parts}x{model}")
    tr = DistributedTrainer(
        build_gcn([ds.in_dim, hidden, ds.num_classes],
                  dropout_rate=0.0), ds, parts, cfg)
    tr.train(epochs=warmup)   # compile lap + warmup
    tr.sync()
    times = []
    for _ in range(epochs):
        t0 = time.perf_counter()
        tr.train(epochs=1)
        tr.sync()
        times.append((time.perf_counter() - t0) * 1e3)
    dev = tr.mesh.devices.flat[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    peak = (stats or {}).get("peak_bytes_in_use")
    return {
        "epoch_ms": round(float(np.median(times)), 2),
        "state_bytes_per_device": state_bytes_on_device(tr, dev),
        "peak_hbm_bytes": int(peak) if peak is not None else None,
        "part_nodes": int(tr.pg.part_nodes),
        "part_edges": int(tr.pg.part_edges),
    }


def mesh_race(ds, num_devices, hidden, epochs):
    """All candidate (parts, model) shapes of ``num_devices`` + the
    1-D-vs-best-2-D summary."""
    from roc_tpu.parallel import candidate_mesh_shapes
    shapes = {}
    for p, m in candidate_mesh_shapes(num_devices):
        shapes[f"{p}x{m}"] = mesh_row(ds, p, m, hidden, epochs)
    one_d = shapes[f"{num_devices}x1"]
    two_d = {k: v for k, v in shapes.items()
             if not k.endswith("x1")}
    best_key = min(two_d, key=lambda k: two_d[k]["epoch_ms"])
    best = two_d[best_key]
    return shapes, {
        "one_d": f"{num_devices}x1",
        "best_2d": best_key,
        "mesh_epoch_ratio": round(
            best["epoch_ms"] / max(one_d["epoch_ms"], 1e-9), 4),
        "state_bytes_ratio": round(
            best["state_bytes_per_device"]
            / max(one_d["state_bytes_per_device"], 1), 4),
        "state_shrunk": bool(best["state_bytes_per_device"]
                             < one_d["state_bytes_per_device"]),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=8192)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--dim", type=int, default=256,
                    help="input feature width (the wide-model regime)")
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--devices", type=int, default=None,
                    help="race this many devices (default: all)")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    import jax
    dev = jax.devices()[0]
    n = args.devices or len(jax.devices())
    print(f"# device={dev.platform} {dev.device_kind} x{n} "
          f"V={args.nodes} F={args.dim} H={args.hidden}",
          file=sys.stderr)
    ds = make_wide_dataset(args.nodes, args.degree, args.dim,
                           args.classes)
    shapes, win = mesh_race(ds, n, args.hidden, args.epochs)
    for k, row in shapes.items():
        print(f"# {k}: epoch {row['epoch_ms']} ms, state/dev "
              f"{row['state_bytes_per_device']} B", file=sys.stderr)
    result = {"device": f"{dev.platform} {dev.device_kind}",
              "num_devices": n, "config": vars(args),
              "shapes": shapes, "win": win}
    line = json.dumps(result, indent=1)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

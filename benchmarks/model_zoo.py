#!/usr/bin/env python
"""One-chip epoch-time measurements for BASELINE.md configs 3-5 shapes.

chip_smoke.py runs the Reddit-shape GCN (config 2) through the CLI.
This script times the remaining model-family configs on
synthetic graphs with the real datasets' V/E/F shapes (epoch time is
independent of edge identity):

  3  GraphSAGE-mean, ogbn-arxiv shape   (169k nodes, 2.3M directed
     edges -> ~4.6M symmetric+self, 128 feats, 40 classes)
  4  GCN, ogbn-products shape           (2.45M nodes, ~126M
     symmetric+self edges, 100 feats, 47 classes) — the reference
     runs this 4-way; one chip is the per-device slice x4 workload
  5  GIN sum-aggregation + MLP, Amazon-2M shape (same graph family as
     products; 2-layer GIN MLP)

Usage: python benchmarks/model_zoo.py [--config 3|4|5] [--epochs N]
Appends results to benchmarks/model_zoo.jsonl.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONFIGS = {
    "3": dict(model="sage", nodes=169_343, edges=4_600_000,
              layers=(128, 256, 40)),
    "4": dict(model="gcn", nodes=2_449_029, edges=126_000_000,
              layers=(100, 256, 47)),
    # 5: GIN's default MLP hidden changed in round 5 (output layer:
    # 47 -> 256; a class-count-wide biasless ReLU bottleneck could die
    # per-class — models/gin.py).  The recorded 6,023 ms mixed record
    # predates the widening and needs a re-measure; the extra
    # [2.45M, 256] activation may also move the fits-in-HBM boundary
    # (the autopilot will say).
    "5": dict(model="gin", nodes=2_449_029, edges=126_000_000,
              layers=(100, 256, 47)),
    # 6: GAT at ogbn-arxiv shape — the attention family (beyond the
    # reference's sum-only aggregation; ops/attention.py).  Attention
    # needs the ELL tables, so impl='auto' resolves through the
    # trainer's resolve_attention_impl override, not the size split.
    "6": dict(model="gat", nodes=169_343, edges=4_600_000,
              layers=(128, 256, 40)),
    # 7: GAT at the products/Amazon-2M shape — the attention capability
    # bound on one chip.  History (v5e, 2026-07-30): the per-width
    # bucket path OOMed its backward residuals (fixed by the scan-body
    # remat in ops/attention.py), then exceeded practical remote
    # compile time (>40 min — one checkpointed scan per width bucket,
    # doubled by autodiff).  The uniform flat8 layout exists for
    # exactly this config (HLO 4849 -> 511 lines, compile_probe.py);
    # with impl left at 'auto' the trainer now routes E=126M attention
    # to 'attn_flat8'.  2026-07-31: the flat8 numerator carry OOMed by
    # 885M at this V/F (fixed by the dh-chunked numerator,
    # resolve_dh_chunk); not re-measured since.
    "7": dict(model="gat", nodes=2_449_029, edges=126_000_000,
              layers=(100, 256, 47)),
    # 8: APPNP at the arxiv shape (beyond reference) — k teleport-
    # anchored propagation hops over the trainer's resolved layout;
    # the hop loop is GCN's hot path with a fused lerp, so epoch time
    # ~ k/2 x the 2-hop SAGE row above plus the (cheap) MLP
    "8": dict(model="appnp", nodes=169_343, edges=4_600_000,
              layers=(128, 256, 40)),
    # 9: GCNII at the arxiv shape, 8 propagation layers (beyond
    # reference) — the deep-stack family; per layer one aggregation +
    # one [V, 256] matmul, so ~4x the 2-hop SAGE row's aggregation
    # count
    "9": dict(model="gcn2",
              nodes=169_343, edges=4_600_000,
              layers=(128,) + (256,) * 8 + (40,)),
}
_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "model_zoo.jsonl")


def run(cfg_key: str, epochs: int, impl: str,
        dtype: str = "float32", heads: int = 1,
        remat: bool = False) -> dict:
    import jax
    from roc_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from roc_tpu.core.graph import Dataset, random_csr
    from roc_tpu.models.gat import build_gat
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.models.appnp import build_appnp
    from roc_tpu.models.gcn2 import build_gcn2
    from roc_tpu.models.gin import build_gin
    from roc_tpu.models.sage import build_sage
    from roc_tpu.train.trainer import TrainConfig, Trainer

    c = CONFIGS[cfg_key]
    layers = list(c["layers"])
    # validate BEFORE the minutes-long synthetic graph generation
    # (same policy as roc_tpu/train/cli.py's up-front flag checks)
    if heads != 1:
        if c["model"] != "gat":
            raise SystemExit(
                f"--heads applies to gat configs only; config "
                f"{cfg_key} is {c['model']}")
        if heads < 1 or any(d % heads for d in layers[1:-1]):
            raise SystemExit(
                f"--heads {heads} invalid for hidden dims {layers[1:-1]}")
    if impl == "auto" and c["model"] != "gat":
        # record the kernel that actually runs, not the CLI alias.
        # GAT configs keep 'auto': the TRAINER's resolver owns the
        # attention routing (ell below ATTN_FLAT8_MIN_EDGES, the
        # uniform flat8 layout above it — it needs the dataset, which
        # this early resolution doesn't have)
        # num_edges arms the flat_sum compile-wall route past the
        # sectioned window (core/ell.py FLAT_SUM_MIN_EDGES) — the
        # products-scale zoo configs are exactly its target
        from roc_tpu.core.ell import resolve_auto_impl
        impl = resolve_auto_impl(c["nodes"], num_edges=c["edges"])
    dev = jax.devices()[0]
    print(f"# config {cfg_key}: {c['model']} V={c['nodes']} "
          f"E={c['edges']} on {dev.device_kind}", file=sys.stderr)
    t0 = time.time()
    graph = random_csr(c["nodes"], c["edges"], seed=0)
    rng = np.random.RandomState(1)
    ds = Dataset(
        graph=graph,
        features=rng.rand(c["nodes"], layers[0]).astype(np.float32),
        labels=rng.randint(0, layers[-1],
                           size=c["nodes"]).astype(np.int32),
        mask=rng.choice([1, 2, 3], size=c["nodes"],
                        p=[0.66, 0.10, 0.24]).astype(np.int32),
        num_classes=layers[-1], name=f"config{cfg_key}-synth")
    print(f"# data gen {time.time()-t0:.0f}s", file=sys.stderr)

    build = {"gcn": build_gcn, "sage": build_sage, "gin": build_gin,
             "gat": build_gat, "appnp": build_appnp,
             "gcn2": build_gcn2}
    kwargs = {"heads": heads} if c["model"] == "gat" else {}
    if c["model"] == "appnp":
        kwargs["k"] = 10  # the paper's classic depth (cli.py default)
    model = build[c["model"]](layers, dropout_rate=0.5, **kwargs)
    # GIN aggregates raw F-wide features (dropout output feeds
    # scatter_gather directly), which the ELL-family impls handle;
    # 'auto' resolves per the measured window (ell at products scale,
    # sectioned at arxiv scale — core/ell.py resolve_auto_impl)
    # memory="auto": the products/Amazon shapes exceed HBM without
    # remat — the autopilot estimates and picks (echoed on stderr)
    # dtype="mixed" = fp32 master params + bf16 compute: at products/
    # Amazon scale this is what makes GIN fit (fp32 + remat still OOMs
    # a 16G chip by ~0.4G) and halves aggregation HBM traffic
    from roc_tpu.train.trainer import resolve_dtypes
    dt, cdt = resolve_dtypes(dtype)
    # --remat forces manual remat (the autopilot's estimator doesn't
    # model attention's extra transients; config 7 needs this)
    tc = TrainConfig(learning_rate=0.01, weight_decay=1e-4,
                     aggr_impl=impl, verbose=True,
                     dtype=dt, compute_dtype=cdt,
                     eval_every=1 << 30, symmetric=True,
                     memory="manual" if remat else "auto",
                     remat=remat)
    t0 = time.time()
    tr = Trainer(model, ds, tc)
    tr.train(epochs=2)
    tr.sync()
    compile_s = time.time() - t0
    print(f"# prep+compile+warmup {compile_s:.0f}s", file=sys.stderr)
    times = []
    for _ in range(epochs):
        t0 = time.time()
        tr.train(epochs=1)
        tr.sync()
        times.append((time.time() - t0) * 1e3)
    rec = {"config": cfg_key, "model": c["model"], "V": c["nodes"],
           "E": int(graph.num_edges), "layers": layers,
           # the trainer's resolved impl, not the CLI alias — e.g.
           # attention models override to 'ell' at setup
           "impl": tr.config.aggr_impl,
           "dtype": dtype,
           **({"heads": heads} if c["model"] == "gat" and heads != 1
              else {}),
           **({"remat": True} if remat else {}),
           "platform": dev.platform, "device_kind": dev.device_kind,
           "epoch_ms": round(float(np.median(times)), 1),
           "epoch_ms_all": [round(t) for t in times],
           "compile_s": round(compile_s, 1),
           "recorded": time.strftime("%Y-%m-%dT%H:%M:%S%z")}
    print(f"# epochs (ms): {rec['epoch_ms_all']}", file=sys.stderr)
    with open(_OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="3",
                    choices=list(CONFIGS))
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--impl", default="auto")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "mixed"])
    ap.add_argument("--heads", type=int, default=1,
                    help="attention heads (gat configs only)")
    ap.add_argument("--remat", action="store_true",
                    help="force remat (skip the memory autopilot)")
    args = ap.parse_args()
    run(args.config, args.epochs, args.impl, args.dtype, args.heads,
        args.remat)


if __name__ == "__main__":
    main()

"""Command-line driver: the reference's flag surface on the new stack.

Mirrors ``parse_input_args`` (``gnn.cc:114-179``) flag for flag —
``-lr``, ``-e/-epoch``, ``-dropout/-dr``, ``-decay/-wd``,
``-decay-rate``, ``-decay-step/-ds``, ``-file``, ``-seed``,
``-verbose/-v`` and the dash-separated ``-layers 602-256-41`` spec
(layers[0] = input dim, layers[-1] = classes) — plus the TPU-side knobs
the Legion low-level flags (``-ll:gpu`` etc.) used to carry: ``--parts``
(graph partitions = mesh size), ``--model`` (gcn/sage/gin), ``--impl``
(aggregation backend), ``--dtype``, ``--checkpoint``/``--resume``.

Run: ``python -m roc_tpu.train.cli -file data/reddit -layers 602-256-41
-lr 0.01 -decay 0.0001 -decay-rate 0.97 -dropout 0.5 -e 3000``
(cf. ``test.sh:8`` / ``example_run.sh:1``).  Without ``-file`` a
synthetic dataset is used (smoke-test mode).
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Callable, List, Optional

from ..core.ell import AGGR_IMPLS


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="roc_tpu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    # reference flags (gnn.cc:114-179); defaults from gnn.cc:30-41
    ap.add_argument("-lr", type=float, default=0.01, dest="lr")
    ap.add_argument("-e", "-epoch", type=int, default=200, dest="epochs")
    ap.add_argument("-dropout", "-dr", type=float, default=0.5,
                    dest="dropout")
    ap.add_argument("-decay", "-wd", type=float, default=0.05,
                    dest="weight_decay")
    ap.add_argument("-decay-rate", type=float, default=1.0,
                    dest="decay_rate")
    ap.add_argument("-decay-step", "-ds", type=int, default=100,
                    dest="decay_steps")
    ap.add_argument("-file", type=str, default=None, dest="file",
                    help="dataset prefix (<prefix>.lux / .feats.csv / "
                         ".label / .mask)")
    ap.add_argument("-layers", type=str, default="16-16-4",
                    help="dash-separated dims, e.g. 602-256-41")
    ap.add_argument("-seed", type=int, default=1)
    ap.add_argument("-verbose", "-v", action="store_true")
    # TPU-era flags
    ap.add_argument("--model",
                    choices=["gcn", "sage", "gin", "gat", "sgc",
                             "appnp", "gcn2", "rgcn", "deepergcn",
                             "gtrans"],
                    default="gcn",
                    help="model family (roc_tpu/models): gcn (the "
                         "reference's), sage, gin, gat, sgc, appnp, "
                         "gcn2 (GCNII / GCNII*, arXiv:2007.02133), "
                         "rgcn (R-GCN on a typed graph, "
                         "arXiv:1703.06103), deepergcn (DeeperGCN, "
                         "arXiv:2006.07739, as deep_gcns_torch's "
                         "examples/ogb/ogbn_arxiv runs it: res+ "
                         "blocks of BatchNorm -> ReLU -> dropout -> "
                         "GENConv with the softmax_sg aggregation; "
                         "-layers F-H-...-H-C, one H a layer), gtrans "
                         "(UniMP's Graph Transformer, arXiv:2009.03509, "
                         "as PyG's TransformerConv with beta: dot-product "
                         "attention over each vertex's neighbours, a "
                         "gated root path, LayerNorm + ReLU between "
                         "layers; -layers F-H-...-C with H the "
                         "concatenated width of --heads heads, the "
                         "output layer's heads C wide and averaged; "
                         "-dropout is the attention dropout)")
    ap.add_argument("--t", type=float, default=None, dest="temperature",
                    help="for --model deepergcn: the temperature of "
                         "the softmax neighbour aggregation (default "
                         "0.1, the ogbn-arxiv command's; fixed, the "
                         "script's learn_t is off)")
    ap.add_argument("--node-types", type=str, default=None,
                    help="for --model rgcn: the typed graph's vertex "
                         "kinds as comma-separated counts in id order "
                         "(kinds are contiguous id ranges; they must "
                         "sum to V).  Kind 0 carries the file's "
                         "features and the labels.  The relations are "
                         "derived: every ordered pair of kinds with a "
                         "non-self edge in the file is one relation")
    ap.add_argument("--embed-types", type=str, default=None,
                    help="for --model rgcn: comma-separated kinds "
                         "(never 0) whose input is a trainable "
                         "embedding table of the input width instead "
                         "of the file's feature rows")
    ap.add_argument("--heads", type=int, default=1,
                    help="attention heads for --model gat (hidden "
                         "dims must divide by it; output layer stays "
                         "single-head) and --model gtrans (every "
                         "layer; the output layer averages them)")
    ap.add_argument("--skip", action="store_true",
                    help="for --model gat: add a bias-free linear map "
                         "of each layer's input to its attention "
                         "output (DGL GATConv's res_fc), output layer "
                         "included")
    ap.add_argument("--act", choices=["elu", "relu"], default=None,
                    help="for --model gat: hidden-layer activation "
                         "(default elu, the paper's)")
    ap.add_argument("--input-dropout", type=float, default=None,
                    help="for --model gat: dropout rate on the raw "
                         "features (default: -dropout, as on every "
                         "other layer)")
    ap.add_argument("--hops", type=int, default=None,
                    help="for --model sgc/appnp: propagation depth k "
                         "(sgc: logits = softmax(S^k X W), default 2; "
                         "appnp: k teleport-anchored hops after the "
                         "MLP, default 10 — the papers' classic "
                         "settings)")
    ap.add_argument("--alpha", type=float, default=None,
                    help="for --model appnp/gcn2: teleport / initial-"
                         "residual strength (default 0.1)")
    ap.add_argument("--lam", type=float, default=None,
                    help="for --model gcn2: identity-mapping decay "
                         "(beta_l = log(lam/l + 1); default 0.5)")
    ap.add_argument("--star", action="store_true",
                    help="for --model gcn2: the GCNII* form, as the "
                         "OGB ogbn-arxiv leaderboard's GCNII rows run "
                         "it — separate weights for the propagated "
                         "and the initial-residual branch, (P H) W1 + "
                         "H_0 W2 (default: GCNII, one shared weight a "
                         "layer)")
    ap.add_argument("--learn-eps", action="store_true",
                    help="for --model gin: learnable per-layer "
                         "epsilon self-weight (zero-init GIN-0) "
                         "instead of the fixed self-add")
    ap.add_argument("--parts", type=int, default=1,
                    help="graph partitions == mesh devices (the "
                         "reference's numMachines*numGPUs)")
    ap.add_argument("--mesh", type=str, default="auto",
                    help="device mesh shape PxM (parts x model), "
                         "e.g. 2x4: P must equal --parts and M > 1 "
                         "feature-shards the params and Adam moments "
                         "over the model axis of the (parts, model) "
                         "2-D mesh (needs P*M devices); 'auto' "
                         "(default) = every device on the parts axis "
                         "— today's exact 1-D behavior")
    ap.add_argument("--impl", default="auto", choices=AGGR_IMPLS,
                    help="aggregation layout; auto chooses from the "
                         "graph's size and the device's measured "
                         "window: 'sectioned' (source-sectioned width-8 "
                         "sub-rows) where the gathered table is past "
                         "one section and the output rows are inside "
                         "the window — or, inside it, 'bdense' (dense "
                         "adjacency tiles) where the structure probe "
                         "finds the edges concentrated in tiles; "
                         "outside it 'flat_sum' (one uniform width-8 "
                         "scan: one compiled program per feature width "
                         "instead of one per degree bucket) at >=20M "
                         "edges, else 'ell' (degree buckets). "
                         "Attention and MAX/MIN models take 'ell' or a "
                         "flat layout. 'segment' is the edge-list "
                         "reference the parity tests compare against")
    ap.add_argument("--fuse", default="auto",
                    choices=["auto", "on", "off"],
                    help="fold norm -> aggregate -> norm [-> relu] "
                         "chains into one fused aggregation op with "
                         "table-baked D^-1/2 scales (exact linear "
                         "algebra; default auto = fuse whenever the "
                         "model has the chain)")
    ap.add_argument("--partition", default="auto",
                    choices=["greedy", "cost", "auto"],
                    help="distributed split-point selection: 'greedy' "
                         "= the reference's edge-count sweep "
                         "(gnn.cc:806-829), 'cost' = cost-balanced "
                         "minimax search over the partition cost "
                         "model's padded-shape surrogate "
                         "(core/costmodel.py), 'auto' (default) = "
                         "cost — never worse than greedy under the "
                         "model, strictly better on skewed graphs")
    ap.add_argument("--rebalance", action="store_true",
                    help="online load rebalancing (--parts > 1): fit "
                         "the per-partition cost model against "
                         "measured step times and repartition at "
                         "epoch boundaries when the predicted "
                         "max-shard gain exceeds 10%% (at most 2 "
                         "repartitions per run; numerics-preserving "
                         "under full-batch training)")
    ap.add_argument("--halo", default="gather",
                    choices=["gather", "ring"],
                    help="distributed halo exchange: one-shot "
                         "all_gather or ppermute ring (O(V/P) memory)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "mixed"],
                    help="float32 = the reference's pure-fp32 "
                         "semantics; bfloat16 = everything (incl. "
                         "params/Adam) in bf16; mixed = fp32 master "
                         "params + bf16 features/activations (halves "
                         "aggregation HBM traffic, MXU-native matmuls)")
    ap.add_argument("--memory", default="auto",
                    choices=["auto", "manual"],
                    help="auto (default): estimate per-device HBM and "
                         "pick halo/features/remat (core/memory.py), "
                         "echoing the decision; explicit --halo/"
                         "--features flags switch back to manual")
    ap.add_argument("--features", default="hbm",
                    choices=["hbm", "host"],
                    help="input-feature residency: device HBM, or host "
                         "RAM streamed through the first layer "
                         "(>HBM graphs, single device)")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize activations in backward")
    ap.add_argument("--prefetch", default="auto",
                    help="streamed-tier staging-pool depth "
                         "(--features host): blocks the background "
                         "stager runs ahead of compute; 'auto' = 1 "
                         "(double-buffered — block k+1's host copy + "
                         "H2D transfer hide under block k's compute), "
                         "0 = synchronous (the parity/debug "
                         "reference).  Epoch records then carry "
                         "overlap_frac / h2d_wait_p50_ms "
                         "(python -m roc_tpu.report)")
    ap.add_argument("--head-chunk", default="auto",
                    help="chunked output head: evaluate the "
                         "classification-head linear as a scan over "
                         "this many vertex rows per block so its "
                         "compiled matmul is [block, C] instead of "
                         "[V_p, C] (bit-identical forward values; "
                         "dW matches to fp32 roundoff); 'auto' "
                         "(default) chunks at 65536 rows once the "
                         "local row count reaches 262144, 0 disables")
    ap.add_argument("--cache-min-secs", type=float, default=None,
                    help="persistent compile cache write threshold "
                         "(seconds): programs compiling faster are "
                         "not persisted.  Default: "
                         "$ROC_TPU_CACHE_MIN_SECS or 1.0; pass 0 to "
                         "persist every program (what `python -m "
                         "roc_tpu.prewarm` does — the 1.0 s default "
                         "silently skips the small per-block "
                         "streamed-head programs)")
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--checkpoint", type=str, default=None,
                    help="save params+opt state here after training")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="also save every N epochs")
    ap.add_argument("--resume", type=str, default=None,
                    help="restore a checkpoint before training")
    ap.add_argument("--recovery", action="store_true",
                    help="checkpoint-restart recovery "
                         "(roc_tpu/resilience): train in checkpointed "
                         "rounds under a keep-last-3 rotation at the "
                         "--checkpoint PREFIX (v3 checkpoint "
                         "directories <prefix>.<epoch>/ with "
                         "per-process shard files and a committed "
                         "MANIFEST.json; legacy .npz checkpoints "
                         "still restore), resume from the "
                         "newest intact checkpoint on start — "
                         "re-invoking the identical command after ANY "
                         "crash continues the run, including onto a "
                         "different --parts (elastic restart) — and "
                         "retry numeric failures / watchdog stalls / "
                         "transient I/O errors from the last good "
                         "checkpoint (bounded by --max-retries).  "
                         "Arms the SIGTERM/SIGINT preemption handler; "
                         "exits 75 (restartable) on preemption")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="recovery retry budget per failure streak "
                         "(--recovery; default 3)")
    ap.add_argument("--preempt-grace", type=float, default=None,
                    dest="preempt_grace",
                    help="arm the SIGTERM/SIGINT preemption handler "
                         "with this grace window in seconds (also "
                         "armed by --recovery, default 30): the first "
                         "signal finishes the in-flight epoch step, "
                         "writes an emergency checkpoint, and exits "
                         "75 (restartable); a second signal kills "
                         "immediately")
    ap.add_argument("--async-save", default="auto",
                    choices=["auto", "on", "off"],
                    dest="async_save",
                    help="asynchronous checkpointing (resilience/"
                         "async_save.py): the recovery rotation's "
                         "saves run CRC+write+commit on a background "
                         "saver thread (bounded queue depth 1, newer "
                         "snapshot supersedes a queued one) — the "
                         "step path pays only the finite guard + "
                         "host snapshot.  'auto' (default) = on when "
                         "single-process, off under multi-process "
                         "SPMD; emergency/preemption saves are "
                         "always flushed before exit")
    ap.add_argument("--fault", type=str, default=None,
                    help="fault-injection drill (resilience/"
                         "inject.py): arm ONE fault as "
                         "site:epoch[:proc] — sites nan_grads, "
                         "sigkill, sigterm, kill_in_save, "
                         "kill_in_async_save, shard_corrupt, "
                         "saver_stall, bitflip_checkpoint, "
                         "staging_io, stall_compile.  Equivalent "
                         "env: ROC_TPU_FAULT")
    ap.add_argument("--eval-only", action="store_true",
                    help="run one inference pass (the reference's "
                         "every-5th-epoch infer, gnn.cc:107-110, as a "
                         "standalone step — typically with --resume) "
                         "and exit")
    ap.add_argument("--save-logits", type=str, default=None,
                    help="write the [V, C] inference logits here "
                         "(.npy, float32, ORIGINAL vertex order even "
                         "under --reorder) after training/eval")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="disable the persistent XLA compilation "
                         "cache (utils/compile_cache.py; default on — "
                         "repeat runs skip the 1-2 min Reddit-scale "
                         "compile)")
    ap.add_argument("--profile-dir", type=str, default=None,
                    help="write a jax.profiler trace of one epoch here")
    ap.add_argument("--metrics", type=str, default=None,
                    help="training-metrics JSONL path (one record per "
                         "eval: loss/accuracies, epoch_ms, eval_ms, "
                         "compile_ms, edges_per_s, tflops_per_s, mfu)")
    ap.add_argument("--events", type=str, default=None,
                    help="structured event-log JSONL path (roc_tpu/"
                         "obs): run manifest, resolve/plan decisions, "
                         "compile cost + modeled-vs-actual HBM, "
                         "per-phase epoch spans, stall heartbeats; "
                         "summarize with `python -m roc_tpu.report`. "
                         "Also settable via ROC_TPU_EVENTS")
    ap.add_argument("--reorder", default="none",
                    choices=["none", "bfs", "lpa"],
                    help="vertex relabeling for gather locality "
                         "(core/reorder.py): clusters neighborhoods "
                         "into narrow id ranges so the sectioned "
                         "layout pads less on community-structured "
                         "graphs ('lpa' = label-propagation "
                         "communities, the ordering --impl bdense "
                         "rides on); metrics are relabeling-invariant")
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None,
         inspect: Optional[Callable[[Any], None]] = None) -> int:
    """The ``roc-tpu-train`` entry point.  ``inspect``, when given, is
    called with the live trainer once the run's work is done and
    before it is dropped — how an in-process caller (chip_smoke.py)
    checks placement and timing on the objects the run actually
    used."""
    args = parse_args(argv)
    from ..obs.events import emit, install_excepthook, span
    # crash flight recorder: an unhandled exception dumps the last
    # telemetry window (obs/events.py ring buffer) before the
    # traceback — dead runs stop taking their evidence with them
    install_excepthook()
    if args.events:
        # env too, so worker/child processes join the same artifact
        import os
        os.environ["ROC_TPU_EVENTS"] = args.events
        from ..obs.events import configure
        configure(jsonl_path=args.events)
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if not args.no_compile_cache:
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache(min_compile_secs=args.cache_min_secs)
    from ..core.graph import load_dataset, synthetic_dataset
    from .trainer import TrainConfig, Trainer, resolve_dtypes
    from ..parallel.distributed import DistributedTrainer
    from ..utils.checkpoint import checkpoint_trainer, restore_trainer

    layers = [int(x) for x in args.layers.split("-")]
    if len(layers) < 2:
        print("error: -layers needs at least in-dim and classes",
              file=sys.stderr)
        return 2
    # flag validation BEFORE the (possibly minutes-long) dataset load
    # ONE validator (train/trainer.py resolve_prefetch) so the CLI and
    # the trainer can never accept different --prefetch vocabularies
    from .trainer import resolve_head_chunk, resolve_prefetch
    try:
        resolve_prefetch(TrainConfig(prefetch=args.prefetch))
    except ValueError as e:
        print(f"error: --prefetch: {e}", file=sys.stderr)
        return 2
    # ONE validator (train/trainer.py resolve_head_chunk), same policy
    # as --prefetch: the CLI and the trainer share the vocabulary
    try:
        resolve_head_chunk(TrainConfig(head_chunk=args.head_chunk),
                           1 << 30)
    except ValueError as e:
        print(f"error: --head-chunk: {e}", file=sys.stderr)
        return 2
    # ONE validator (train/trainer.py resolve_mesh) again: the CLI,
    # both trainers, multihost, and the rigs share the PxM vocabulary
    from .trainer import resolve_mesh
    try:
        resolve_mesh(TrainConfig(mesh=args.mesh),
                     num_parts=max(args.parts, 1))
    except ValueError as e:
        print(f"error: --mesh: {e}", file=sys.stderr)
        return 2
    if args.rebalance and args.parts <= 1:
        print("error: --rebalance requires --parts > 1 (rebalancing "
              "moves partition boundaries over a device mesh)",
              file=sys.stderr)
        return 2
    if args.recovery and not args.checkpoint:
        print("error: --recovery needs --checkpoint PREFIX (the "
              "rotation writes <prefix>.<epoch>/ checkpoint "
              "directories there)", file=sys.stderr)
        return 2
    if args.max_retries < 0:
        print("error: --max-retries must be >= 0", file=sys.stderr)
        return 2
    if args.fault:
        # fail fast on a typo'd drill spec, before the dataset load
        from ..resilience import inject
        try:
            inject.parse(args.fault)
        except ValueError as e:
            print(f"error: --fault: {e}", file=sys.stderr)
            return 2
    if args.model not in ("gat", "gtrans") and args.heads != 1:
        print("error: --heads applies to --model gat/gtrans only",
              file=sys.stderr)
        return 2
    if args.model != "gat" and (args.skip or args.act is not None
                                or args.input_dropout is not None):
        print("error: --skip/--act/--input-dropout apply to --model "
              "gat only", file=sys.stderr)
        return 2
    if args.learn_eps and args.model != "gin":
        print("error: --learn-eps applies to --model gin only",
              file=sys.stderr)
        return 2
    if args.alpha is not None and args.model not in ("appnp", "gcn2"):
        # None sentinel: ANY explicit --alpha on a model without the
        # knob is the misuse this guard exists for, the default value
        # included
        print("error: --alpha applies to --model appnp/gcn2 only",
              file=sys.stderr)
        return 2
    if args.lam is not None and args.model != "gcn2":
        print("error: --lam applies to --model gcn2 only",
              file=sys.stderr)
        return 2
    if args.star and args.model != "gcn2":
        print("error: --star applies to --model gcn2 only",
              file=sys.stderr)
        return 2
    if args.hops is not None and args.model not in ("sgc", "appnp"):
        # same sentinel policy as --alpha/--heads/--learn-eps: a
        # propagation depth on a fixed-depth model must fail, not be
        # silently discarded
        print("error: --hops applies to --model sgc/appnp only",
              file=sys.stderr)
        return 2
    if args.model in ("sgc", "appnp"):
        if args.hops is None:
            args.hops = 2 if args.model == "sgc" else 10
        if args.hops < 1:
            print("error: --hops must be >= 1", file=sys.stderr)
            return 2
    if args.model in ("appnp", "gcn2"):
        if args.alpha is None:
            args.alpha = 0.1
        if not 0.0 <= args.alpha <= 1.0:
            print("error: --alpha must be in [0, 1]", file=sys.stderr)
            return 2
    if args.model == "gcn2":
        if args.lam is None:
            args.lam = 0.5
        if args.lam <= 0.0:
            print("error: --lam must be > 0", file=sys.stderr)
            return 2
        # structural -layers checks up front (same policy as gat's
        # heads divisibility: fail BEFORE the dataset load, with the
        # clean exit-2 contract, not a build_gcn2 traceback after it)
        if len(layers) < 3:
            print("error: gcn2 needs at least one hidden layer "
                  "(F-H-C)", file=sys.stderr)
            return 2
        if any(h != layers[1] for h in layers[1:-1]):
            print(f"error: gcn2 hidden widths must all match (the "
                  f"initial residual adds H_0 into every layer), got "
                  f"{layers[1:-1]}", file=sys.stderr)
            return 2
    if args.temperature is not None and args.model != "deepergcn":
        print("error: --t applies to --model deepergcn only",
              file=sys.stderr)
        return 2
    if args.model == "deepergcn":
        if args.temperature is None:
            args.temperature = 0.1
        if args.temperature <= 0.0:
            print("error: --t must be > 0", file=sys.stderr)
            return 2
        if len(layers) < 3:
            print("error: deepergcn needs at least one GENConv layer "
                  "(F-H-C)", file=sys.stderr)
            return 2
        if any(h != layers[1] for h in layers[1:-1]):
            print(f"error: deepergcn hidden widths must all match (the "
                  f"residual adds h^l into every layer), got "
                  f"{layers[1:-1]}", file=sys.stderr)
            return 2
    node_types = embed_types = ()
    if (args.node_types is not None
            or args.embed_types is not None) and args.model != "rgcn":
        print("error: --node-types/--embed-types apply to --model rgcn "
              "only (the other families read one homogeneous graph)",
              file=sys.stderr)
        return 2
    if args.model == "rgcn":
        from ..core.relations import parse_kinds
        if args.node_types is None:
            print("error: --model rgcn needs --node-types (the typed "
                  "graph's kind counts, in id order)", file=sys.stderr)
            return 2
        try:
            node_types = parse_kinds(args.node_types, "--node-types")
            embed_types = (parse_kinds(args.embed_types, "--embed-types")
                           if args.embed_types else ())
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        bad = [k for k in embed_types if not 0 < k < len(node_types)]
        if bad or min(node_types) < 1:
            print(f"error: --embed-types {bad} name no kind of "
                  f"{len(node_types)} (kind 0 carries the file's "
                  f"features and the labels), or an empty kind in "
                  f"--node-types", file=sys.stderr)
            return 2
        # out of scope for a typed graph, each refused by name
        if args.parts > 1:
            print("error: --model rgcn runs on one chip: a typed graph "
                  "has no vertex partitioner and no distributed step "
                  "yet (--parts 1)", file=sys.stderr)
            return 2
        if args.halo == "ring":
            print("error: --halo ring is a --parts > 1 exchange; a "
                  "typed graph (--model rgcn) runs on one chip",
                  file=sys.stderr)
            return 2
        if args.reorder != "none":
            print("error: --reorder would scatter a typed graph's "
                  "kinds, which are contiguous id ranges",
                  file=sys.stderr)
            return 2
        if args.impl not in ("auto", "flat_sum", "segment"):
            print(f"error: the relation aggregation has no "
                  f"{args.impl!r} layout; --impl takes auto, flat_sum "
                  f"or segment for --model rgcn", file=sys.stderr)
            return 2
    if args.model in ("gat", "gtrans"):
        if args.heads < 1:
            print("error: --heads must be >= 1", file=sys.stderr)
            return 2
        bad = [d for d in layers[1:-1] if d % args.heads]
        if bad:
            print(f"error: hidden dims {bad} not divisible by "
                  f"--heads {args.heads}", file=sys.stderr)
            return 2
        if args.input_dropout is not None and \
                not 0.0 <= args.input_dropout < 1.0:
            print("error: --input-dropout must be in [0, 1)",
                  file=sys.stderr)
            return 2
    if args.model == "gtrans":
        if not 0.0 <= args.dropout < 1.0:
            print("error: -dropout (the attention dropout of --model "
                  "gtrans) must be in [0, 1)", file=sys.stderr)
            return 2
        if args.parts > 1:
            from ..models.builder import TFATTN_PARTITION_REFUSAL
            print(f"error: {TFATTN_PARTITION_REFUSAL}", file=sys.stderr)
            return 2

    # set-up's phases go under spans (obs/events.py span) from here on;
    # the trainer's constructor flushes them with its own as one batch
    with span("setup.load") as s:
        if args.file:
            ds = load_dataset(args.file, in_dim=layers[0],
                              num_classes=layers[-1])
        else:
            ds = synthetic_dataset(512, 8, in_dim=layers[0],
                                   num_classes=layers[-1], seed=args.seed)
        s.update(nodes=ds.graph.num_nodes, edges=ds.graph.num_edges)
    if args.model == "rgcn":
        from ..core.relations import derive_typed
        try:
            with span("setup.typed") as s:
                ds.typed = derive_typed(ds.graph, node_types)
                s.update(relations=len(ds.typed.relations),
                         edges=ds.typed.num_edges)
        except ValueError as e:
            print(f"error: --node-types: {e}", file=sys.stderr)
            return 2
    perm = None
    if args.reorder != "none":
        from ..core.reorder import ORDERINGS, apply_vertex_order
        with span("setup.reorder") as s:
            ds, perm = apply_vertex_order(
                ds, ORDERINGS[args.reorder](ds.graph),
                order_name=args.reorder)
        emit("plan", f"reorder={args.reorder} applied in "
             f"{s.ms / 1e3:.1f}s", reorder=args.reorder,
             reorder_s=round(s.ms / 1e3, 2))
    # config echo, like gnn.cc:48-60 (the structured run manifest is
    # emitted by the trainer once the config is RESOLVED)
    emit("run", f"dataset={ds.name} V={ds.graph.num_nodes} "
         f"E={ds.graph.num_edges} layers={layers} model={args.model} "
         f"lr={args.lr} wd={args.weight_decay} dropout={args.dropout} "
         f"decay={args.decay_rate}/{args.decay_steps} parts={args.parts} "
         f"mesh={args.mesh} impl={args.impl}")

    from ..models import model_builders
    build = model_builders()
    kwargs = {}
    if args.model == "gat":
        kwargs = {"heads": args.heads, "skip": args.skip,
                  "activation": args.act or "elu",
                  "input_dropout": args.input_dropout}
    if args.model == "gin" and args.learn_eps:
        kwargs["learn_eps"] = True
    if args.model in ("sgc", "appnp"):
        kwargs["k"] = args.hops
    if args.model in ("appnp", "gcn2"):
        kwargs["alpha"] = args.alpha
    if args.model == "gcn2":
        kwargs["lam"] = args.lam
        kwargs["star"] = args.star
    if args.model == "rgcn":
        kwargs = {"node_types": node_types, "embed_types": embed_types,
                  "relations": ds.typed.relations}
    if args.model == "deepergcn":
        kwargs["t"] = args.temperature
    if args.model == "gtrans":
        kwargs["heads"] = args.heads
    model = build[args.model](layers, dropout_rate=args.dropout,
                              **kwargs)
    dt, cdt = resolve_dtypes(args.dtype)
    memory = args.memory
    if memory == "auto" and (args.halo != "gather"
                             or args.features != "hbm" or args.remat):
        # explicit residency flags win over the autopilot
        memory = "manual"
    cfg = TrainConfig(
        learning_rate=args.lr, weight_decay=args.weight_decay,
        dropout_rate=args.dropout, decay_rate=args.decay_rate,
        decay_steps=args.decay_steps, epochs=args.epochs,
        seed=args.seed, eval_every=args.eval_every, verbose=True,
        aggr_impl=args.impl, aggr_fuse=args.fuse, halo=args.halo,
        memory=memory, features=args.features, remat=args.remat,
        prefetch=args.prefetch, partition=args.partition,
        rebalance=args.rebalance, head_chunk=args.head_chunk,
        cache_min_compile_secs=args.cache_min_secs,
        async_save=args.async_save, fault=args.fault, mesh=args.mesh,
        dtype=dt, compute_dtype=cdt, metrics_path=args.metrics)

    from ..obs.heartbeat import StallFailure
    from ..resilience import preempt
    from ..resilience.preempt import Preempted, RESTARTABLE_EXIT_CODE
    if args.recovery or args.preempt_grace is not None:
        preempt.install(args.preempt_grace
                        if args.preempt_grace is not None
                        else preempt.DEFAULT_GRACE_S)

    if args.halo == "ring" and args.parts <= 1:
        print("error: --halo ring requires --parts > 1 (the ring "
              "rotates shards over a device mesh)", file=sys.stderr)
        return 2
    try:
        if args.parts > 1:
            trainer = DistributedTrainer(model, ds, args.parts, cfg)
        else:
            trainer = Trainer(model, ds, cfg)
    except StallFailure as e:
        # a watchdog-promoted setup hang (dead multihost peer at the
        # DCN rendezvous, wedged first table build) is restartable —
        # a fresh process against a recovered fleet IS the retry
        emit("resilience", f"{e} during trainer setup — exiting "
             f"{RESTARTABLE_EXIT_CODE} (restartable)",
             kind="restartable_exit")
        return RESTARTABLE_EXIT_CODE

    if args.resume:
        restore_trainer(trainer, args.resume)
        emit("run", f"resumed from {args.resume} at epoch "
             f"{trainer.epoch}", epoch=trainer.epoch)

    def save_logits():
        if not args.save_logits:
            return
        import numpy as np
        logits = np.asarray(trainer.predict(), dtype=np.float32)
        if perm is not None:
            # rows are in reordered coordinates; new row i holds old
            # vertex perm[i] — scatter back to original order
            out = np.empty_like(logits)
            out[perm] = logits
            logits = out
        np.save(args.save_logits, logits)
        emit("run", f"logits [{logits.shape[0]}, {logits.shape[1]}] "
             f"saved to {args.save_logits}", path=args.save_logits)

    if args.eval_only:
        from .trainer import format_metrics
        m = trainer.evaluate()
        print(format_metrics(trainer.epoch, m))
        save_logits()
        if inspect is not None:
            inspect(trainer)
        return 0

    if args.profile_dir:
        trainer.train(epochs=1)  # compile outside the trace
        # phase spans route through jax.profiler.TraceAnnotation for
        # the traced epoch (utils/profiling.py EpochTimer.annotate),
        # so the trace's host plane carries the same named phases as
        # the host timeline lanes.  The CLI owns the toggle here: it
        # never sets TrainConfig.profile_dir (run_epoch_loop would
        # start a SECOND nested profiler trace), so the constructor's
        # annotate-arming path does not apply and the flag is scoped
        # to exactly the traced epoch
        trainer.timer.annotate = True
        try:
            with jax.profiler.trace(args.profile_dir):
                trainer.train(epochs=1)
        finally:
            trainer.timer.annotate = False
        emit("run", f"profile written to {args.profile_dir}",
             path=args.profile_dir)
        # the trace names device operations by HLO instruction; the
        # instruction -> program-scope map of every step program that
        # ran goes beside it (obs/scopes.py, README "Observability")
        import json
        import os
        from ..obs.compile_watch import ObservedJit
        for step in vars(trainer).values():
            got = (step.instruction_scopes()
                   if isinstance(step, ObservedJit) else None)
            if got is not None:
                with open(os.path.join(
                        args.profile_dir,
                        f"scopes.{step.name}.json"), "w") as f:
                    json.dump(got, f)

    t0 = time.time()
    remaining = args.epochs - trainer.epoch
    try:
        if args.recovery:
            from ..resilience.recovery import (CheckpointRotation,
                                               train_with_recovery)
            from .trainer import resolve_async_save
            rotation = CheckpointRotation(
                args.checkpoint, keep=3,
                async_save=resolve_async_save(cfg))
            every = (args.checkpoint_every if args.checkpoint_every > 0
                     else max(args.eval_every, 1))
            train_with_recovery(trainer, args.epochs, rotation,
                                checkpoint_every=every,
                                max_retries=args.max_retries)
        elif args.checkpoint and args.checkpoint_every > 0:
            while trainer.epoch < args.epochs:
                n = min(args.checkpoint_every,
                        args.epochs - trainer.epoch)
                trainer.train(epochs=n)
                checkpoint_trainer(trainer, args.checkpoint)
        else:
            trainer.train(epochs=max(remaining, 0))
    except Preempted as e:
        # --recovery already wrote the emergency checkpoint through
        # its rotation; the plain path persists --checkpoint here
        # (the finite guard may refuse a poisoned state — still exit
        # restartable, the restart simply starts from whatever good
        # checkpoint exists)
        if not args.recovery and args.checkpoint:
            from ..resilience.recovery import NumericFailure
            try:
                checkpoint_trainer(trainer, args.checkpoint)
            except (NumericFailure, OSError) as nf:
                # a refused (poisoned) or unwritable emergency save
                # must not cost the restartable exit code — the
                # restart resumes from whatever good checkpoint exists
                emit("resilience", f"emergency checkpoint failed: "
                     f"{nf}", kind="preempt", epoch=trainer.epoch)
        emit("resilience", f"preempted at epoch {trainer.epoch} "
             f"({e}) — exiting {RESTARTABLE_EXIT_CODE} (restartable)",
             kind="restartable_exit", epoch=trainer.epoch)
        return RESTARTABLE_EXIT_CODE
    except StallFailure as e:
        # watchdog-promoted hang with nothing restored to retry from:
        # a fresh process (same command) IS the retry
        emit("resilience", f"{e} — exiting {RESTARTABLE_EXIT_CODE} "
             f"(restartable)", kind="restartable_exit",
             epoch=trainer.epoch)
        return RESTARTABLE_EXIT_CODE
    except OSError as e:
        if not args.recovery:
            raise
        emit("resilience", f"I/O failure {e!r} — exiting "
             f"{RESTARTABLE_EXIT_CODE} (restartable)",
             kind="restartable_exit", epoch=trainer.epoch)
        return RESTARTABLE_EXIT_CODE
    dt = time.time() - t0
    if remaining > 0:
        emit("run", f"{remaining} epochs in {dt:.1f}s "
             f"({1000.0 * dt / max(remaining, 1):.1f} ms/epoch)",
             epochs=remaining, wall_s=round(dt, 2))
    if args.checkpoint and not args.recovery:
        # under --recovery the rotation already holds the final state
        # (and --checkpoint is a prefix there, not a file)
        checkpoint_trainer(trainer, args.checkpoint)
        emit("run", f"checkpoint saved to {args.checkpoint}",
             path=args.checkpoint)
    save_logits()
    if inspect is not None:
        inspect(trainer)
    return 0


if __name__ == "__main__":
    sys.exit(main())

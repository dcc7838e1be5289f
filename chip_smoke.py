#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer still starts on
the chip.

One process, no arguments: generate a Reddit-shape learnable symmetric
graph from a seed, train the Reddit GCN (``602-256-41``, the
reference's ``example_run.sh`` flags, ``--dtype mixed --impl auto``)
for a few epochs through the normal entry point — ``roc_tpu.train.cli
.main`` in-process, the ``roc-tpu-train`` script — then read back the
events and metrics the run wrote and check them: it ran on a TPU whose
kind the device tables know, ``auto`` resolved to ``sectioned``, the
native library loaded, the loss is finite and below the untrained
model's (an ``--eval-only`` pass at epoch 0), the compile
cache gained entries, and ``jax.block_until_ready`` is an honest
barrier.  Stdout ends with two JSON lines: ``{"report": {...}}`` —
versions, shape, compile seconds, per-epoch ms, cache entries — and,
last, the pass line ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.  The pass line is printed only when
every phase passed; any failure is an uncaught exception and a
non-zero exit.

    python chip_smoke.py              # one chip
    python chip_smoke.py --parts 4    # one process driving four chips
    python chip_smoke.py --rehearsal  # tiny, CPU, never the pass line

Without a TPU it exits non-zero and names the platform it found.  The
timings it prints describe this run; they are not benchmark metrics.
"""

from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import math
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))

# The Reddit GCN: widths are never cut.  V and the average degree are
# Reddit's (232,965 nodes; ~493 so E ~ 112M once the symmetrized random
# edges are deduplicated and every vertex has its self edge).
LAYERS = "602-256-41"
IN_DIM, CLASSES = 602, 41
DTYPE = "mixed"
FULL = {"nodes": 232_965, "avg_degree": 493}
TINY = {"nodes": 2_048, "avg_degree": 12}      # --rehearsal only
SEED = 0
# evals land on epochs 3 and 7 (train/trainer.py run_epoch_loop): the
# first carries the compile lap and three steady epochs, the second
# four more — and epochs 8..10 run no eval, which the barrier
# comparison below relies on
EPOCHS, EVAL_EVERY = 8, 4


class SmokeFailure(Exception):
    """A check did not hold.  Never caught: it ends the run non-zero."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def parse_args(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parts", type=int, default=1,
                    help="graph partitions = devices (1 or 4); > 1 "
                         "adds the placement, balance and P=1-vs-P "
                         "eval-parity checks")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"),
        help="where --events and --metrics go (default: "
             "chiprun_out/chip_smoke, the directory the chip tool "
             "copies back)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny graph on the CPU backend: exercises "
                         "this script's own control flow, proves "
                         "nothing about the chip, prints "
                         '"rehearsal": true and never the pass line')
    return ap.parse_args(argv)


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def compile_seconds(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """{step name: lower/compile seconds} from a run's compile events
    (cold they are XLA's compile; warm, the persistent cache's load)."""
    return {e["name"]: {"lower_s": e["lower_s"], "compile_s": e["compile_s"]}
            for e in events
            if e.get("cat") == "compile" and "compile_s" in e}


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def make_dataset(shape: Dict[str, int]) -> str:
    """Generate + save the dataset; returns its prefix.  Under the
    checkout's ignored ``data/`` — about 1 GB at full size, so never
    under the directory the chip tool copies back."""
    from roc_tpu.core.graph import save_dataset, synthetic_dataset
    ds = synthetic_dataset(shape["nodes"], shape["avg_degree"],
                           in_dim=IN_DIM, num_classes=CLASSES, seed=SEED,
                           name="reddit_shape")
    d = os.path.join(ROOT, "data", "chip_smoke")
    os.makedirs(d, exist_ok=True)
    prefix = os.path.join(
        d, f"reddit_shape_v{shape['nodes']}_d{shape['avg_degree']}")
    save_dataset(ds, prefix, csv=False)
    return prefix


def cli_args(prefix: str, out: str, tag: str, parts: int,
             eval_only: bool = False) -> List[str]:
    """scripts/example_run.sh's configuration, cut to a few epochs."""
    args = ["-file", prefix, "-layers", LAYERS, "-lr", "0.01",
            "-decay", "0.0001", "-decay-rate", "0.97", "-dropout", "0.5",
            "--dtype", DTYPE, "--impl", "auto",
            "-e", str(EPOCHS), "--eval-every", str(EVAL_EVERY),
            # persist every program: the second run of a call must
            # find ALL of them, not only the slow ones
            "--cache-min-secs", "0",
            "--events", os.path.join(out, f"{tag}.events.jsonl"),
            "--metrics", os.path.join(out, f"{tag}.metrics.jsonl")]
    if parts > 1:
        args += ["--parts", str(parts)]
    if eval_only:
        args += ["--eval-only"]
    return args


def run_cli(args: List[str], inspect) -> None:
    from roc_tpu.obs.events import configure
    from roc_tpu.train import cli
    for flag in ("--events", "--metrics"):
        path = args[args.index(flag) + 1]
        if os.path.exists(path):
            os.remove(path)         # both sinks append
    try:
        rc = cli.main(args, inspect=inspect)
    finally:
        configure(jsonl_path=None)  # close this run's events sink
    check(rc == 0, f"roc_tpu.train.cli.main exited {rc}")
    gc.collect()                    # the trainer's cycles hold HBM


# ------------------------------------------------------------- checks

def compare_barriers(trainer) -> Dict[str, float]:
    """One training step timed to ``jax.block_until_ready`` and one
    timed to a host fetch of a scalar reduced from the result (the
    barrier this repo used before): they must agree to within the
    fetch's own cost, or ``utils/profiling.sync`` — and with it every
    ``epoch_ms`` — is measuring the enqueue."""
    import jax
    import jax.numpy as jnp

    def fetch(tree) -> None:
        float(jnp.sum(jax.tree_util.tree_leaves(tree)[0]))

    def step_ms(barrier) -> float:
        check(trainer.epoch % EVAL_EVERY != EVAL_EVERY - 1,
              "barrier comparison would time an eval")
        t0 = time.perf_counter()
        trainer.train(epochs=1)
        barrier(trainer.params)
        return (time.perf_counter() - t0) * 1e3

    jax.block_until_ready(trainer.params)
    fetch(trainer.params)                   # compile the reduction
    fetch_ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        fetch(trainer.params)               # nothing in flight
        fetch_ms.append((time.perf_counter() - t0) * 1e3)
    block_a = step_ms(jax.block_until_ready)
    fetched = step_ms(fetch)
    block_b = step_ms(jax.block_until_ready)
    block = (block_a + block_b) / 2
    own = statistics.median(fetch_ms)
    # the failure this guards against is gross — a barrier that
    # returns at enqueue makes the blocked step ~0 ms — so the band is
    # the fetch's cost plus generous run-to-run noise
    slack = own + max(abs(block_a - block_b), 0.10 * block) + 5.0
    out = {"step_block_ms": round(block, 2),
           "step_fetch_ms": round(fetched, 2),
           "fetch_alone_ms": round(own, 3),
           "allowed_gap_ms": round(slack, 2)}
    check(abs(fetched - block) <= slack,
          f"block_until_ready and the fetch barrier disagree: {out}")
    return out


def placement(trainer, parts: int) -> Dict[str, Any]:
    """Every sharded array spans all ``parts`` devices, and the bytes
    each device holds are non-zero and within 2x of each other —
    nothing quietly landed on device 0."""
    import jax
    arrays = [a for a in jax.tree_util.tree_leaves(
        (trainer.data.__dict__, trainer.params, trainer.opt_state))
        if isinstance(a, jax.Array)]
    check(len(arrays) > 8, f"only {len(arrays)} device arrays found")
    for a in arrays:
        check(len(a.sharding.device_set) == parts,
              f"{a.dtype}{list(a.shape)} lives on "
              f"{len(a.sharding.device_set)} device(s), not {parts}")
    in_use = []
    for d in jax.devices()[:parts]:
        stats = d.memory_stats()
        if stats is None:           # the CPU backend reports none
            return {"arrays": len(arrays), "bytes_in_use": None}
        in_use.append(int(stats["bytes_in_use"]))
    check(min(in_use) > 0, f"a device holds nothing: {in_use}")
    check(max(in_use) <= 2 * min(in_use),
          f"device bytes differ by more than 2x: {in_use}")
    return {"arrays": len(arrays), "bytes_in_use": in_use}


def check_run(tag: str, out: str, parts: int, rehearsal: bool,
              untrained_loss: float) -> Dict[str, Any]:
    """Read the events and metrics the training run wrote; assert."""
    events = read_jsonl(os.path.join(out, f"{tag}.events.jsonl"))
    metrics = read_jsonl(os.path.join(out, f"{tag}.metrics.jsonl"))

    def only(cat: str, **match) -> Dict[str, Any]:
        got = [e for e in events if e.get("cat") == cat and all(
            e.get(k) == v for k, v in match.items())]
        check(len(got) == 1, f"{len(got)} {cat} events match {match}")
        return got[0]

    man = only("manifest")
    impl = man["resolved"]["aggr_impl"]
    check(man["native"]["loaded"], f"native library: {man['native']}")
    check(man["resolved"]["num_parts"] == parts, str(man["resolved"]))
    if not rehearsal:
        check(man["platform"] == "tpu", f"manifest: {man['platform']}")
        check(impl == "sectioned", f"auto resolved to {impl!r}")
    degraded = [e["msg"] for e in events if e.get("degraded")]
    check(not degraded, f"compile observer degraded: {degraded}")
    step = "dist_train_step" if parts > 1 else "train_step"
    compiles = compile_seconds(events)
    check(step in compiles, f"no compile event for {step}")
    plan = only("plan", fits=True)

    check(len(metrics) == EPOCHS // EVAL_EVERY,
          f"{len(metrics)} evals, expected {EPOCHS // EVAL_EVERY}")
    losses = [untrained_loss] + [m["train_loss"] for m in metrics]
    check(all(math.isfinite(x) for x in losses),
          f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(metrics[-1]["train_acc"] > 1.0 / CLASSES,
          f"train accuracy {metrics[-1]['train_acc']} <= chance")
    # the loop's own accounting must add up to the wall clock the CLI
    # measured around it; if the barrier returned early, epoch_ms
    # would be the enqueue and the evals would absorb the difference
    wall_ms = only("run", epochs=EPOCHS)["wall_s"] * 1e3
    steady = [(EVAL_EVERY - 1 if i == 0 else EVAL_EVERY) * m["epoch_ms"]
              for i, m in enumerate(metrics)]
    accounted = (metrics[0]["compile_ms"] + sum(steady)
                 + sum(m["eval_ms"] for m in metrics))
    check(abs(wall_ms - accounted) <= 0.05 * wall_ms + 50.0,
          f"epoch accounting {accounted:.0f} ms vs wall {wall_ms:.0f} ms")
    return {
        "V": man["dataset"]["num_nodes"], "E": man["dataset"]["num_edges"],
        "layers": LAYERS, "dtype": DTYPE, "parts": parts,
        "resolved_impl": impl,
        "memory_plan": {k: plan[k] for k in
                        ("halo", "features", "remat", "est_bytes",
                         "budget_bytes")},
        "native_loaded": man["native"]["loaded"],
        "compile": compiles,
        "first_step_ms": round(metrics[0]["compile_ms"], 1),
        "epoch_ms": [round(m["epoch_ms"], 2) for m in metrics],
        "eval_ms": [round(m["eval_ms"], 2) for m in metrics],
        "first_loss": losses[0], "last_loss": losses[-1],
        "eval_losses": losses,
        "train_acc": metrics[-1]["train_acc"],
    }


# --------------------------------------------------------------- main

def device_of(devs) -> Dict[str, Any]:
    """The device as JAX reports it."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def pass_line(devs) -> str:
    """The last line of a passing run's stdout: these keys, no others."""
    return json.dumps({"ok": True, "device": device_of(devs)})


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.parts not in (1, 4) and not args.rehearsal:
        print("chip_smoke: --parts is 1 or 4", file=sys.stderr)
        return 2
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.parts > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.parts}")
    import jax
    if args.rehearsal:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    device = device_of(devs)
    if device["platform"] != "tpu" and not args.rehearsal:
        print(f"chip_smoke: no TPU: JAX found platform "
              f"{device['platform']!r} ({device['kind']!r} x "
              f"{device['count']}); nothing was run", file=sys.stderr)
        return 2
    check(len(devs) >= args.parts,
          f"--parts {args.parts} on {len(devs)} device(s)")
    sys.path.insert(0, ROOT)
    import jaxlib
    from roc_tpu import native
    from roc_tpu.core.ell import sectioned_bounds
    from roc_tpu.utils.compile_cache import resolve_cache_dir

    t_start = time.perf_counter()
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    # the device tables must know this chip (they raise otherwise);
    # the library is built from native/rocio.cc, not found
    sectioned_bounds()
    native.rebuild()
    cache_dir = resolve_cache_dir()
    cache_before = cache_entries(cache_dir)

    shape = TINY if args.rehearsal else FULL
    t0 = time.perf_counter()
    prefix = make_dataset(shape)
    dataset_s = time.perf_counter() - t0

    # The untrained loss, through the same entry point (--eval-only at
    # epoch 0): what the trained loss must fall below — this graph is
    # learnt within a step or two, so by the first in-training eval
    # the loss already sits at its noise floor.  At P > 1 also at
    # P = 1: the partitioned forward is the same function.
    result: Dict[str, Any] = {}
    eval0: Dict[int, float] = {}
    for p in sorted({args.parts, 1}, reverse=True):
        run_cli(cli_args(prefix, out_dir, f"eval_p{p}", p, eval_only=True),
                lambda tr, p=p: eval0.__setitem__(
                    p, tr.evaluate()["train_loss"]))
    result["eval0_loss"] = {f"p{p}": v for p, v in eval0.items()}
    result["eval0_compile"] = compile_seconds(read_jsonl(os.path.join(
        out_dir, f"eval_p{args.parts}.events.jsonl")))
    if args.parts > 1:
        rel = abs(eval0[args.parts] - eval0[1]) / abs(eval0[1])
        check(rel <= 1e-2, f"epoch-0 eval loss P={args.parts} vs P=1 "
                           f"differs by {rel:.3e}: {eval0}")
        result["eval0_rel_diff"] = rel

    def inspect(trainer) -> None:
        if args.parts > 1:
            result["placement"] = placement(trainer, args.parts)
        result["barrier"] = compare_barriers(trainer)

    tag = f"train_p{args.parts}"
    run_cli(cli_args(prefix, out_dir, tag, args.parts), inspect)
    result.update(check_run(tag, out_dir, args.parts, args.rehearsal,
                            untrained_loss=eval0[args.parts]))

    cache_after = cache_entries(cache_dir)
    check(cache_after > 0, f"compile cache {cache_dir} is empty")
    stats = devs[0].memory_stats() or {}
    line = {
        "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": importlib.metadata.version("libtpu")},
        **result,
        "dataset_s": round(dataset_s, 1),
        "cache": {"dir": cache_dir, "entries_before": cache_before,
                  "entries_after": cache_after,
                  "new_entries": cache_after - cache_before},
        "memory": {"bytes_limit": stats.get("bytes_limit"),
                   "peak_bytes_in_use": stats.get("peak_bytes_in_use")},
        "wall_s": round(time.perf_counter() - t_start, 1),
        "events": os.path.join(out_dir, f"{tag}.events.jsonl"),
    }
    if args.rehearsal:
        print(json.dumps({"rehearsal": True, **line}), flush=True)
        return 0
    # what the run found, then — last, and only when every phase
    # passed — the pass line: exactly these keys, the device as JAX
    # reports it
    print(json.dumps({"report": line}), flush=True)
    print(pass_line(devs), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Driver ``train_job``: one full-graph training job, run through the
program's own entry point.

A traffic mix of this driver says which substrate, how many partitions
and the eval cadence.  The job is a closed loop by nature (the reference
dispatches epochs back to back and prints an inference pass every fifth,
``gnn.cc:99-111``), so after warm-up the window repeats

    burst of ``eval_every`` epochs  ->  ``sync``  ->  ``evaluate()``

under the benchmark's own clock until the next burst would overrun
``--seconds``; it starts no burst it expects not to finish (but always
one).

What is measured is what a user who types the documented command gets:
``roc_tpu.train.cli.main(argv, inspect=...)`` with the configuration's
published flags, ``--impl auto --memory auto`` and no tuning flag.  The
CLI is given ``-e 0`` and an eval cadence it never reaches, so it loads
the dataset, resolves the configuration and builds the normal trainer,
and then hands that trainer to ``inspect``: warm-up, window, traced
stretch and per-layer readers all run there, on the trainer the CLI
built, through ``trainer.train`` / ``sync`` / ``evaluate`` / ``predict``.
The plain-reference check runs after the CLI has returned and dropped
the trainer.
"""

from __future__ import annotations

import gc
import glob
import importlib.metadata
import json
import math
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from harness import cells, dataset, device, trace as trace_mod

NEVER = 1_000_000_000          # an eval cadence no run reaches
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass
class Run:
    """What one run knows; the per-layer readers get all of it."""
    cell: Any
    args: Any
    devs: List[Any]
    rehearsal: bool
    peaks: Optional[Dict[str, Any]]
    data: Any = None                      # harness.dataset.Prepared
    trainer: Any = None                   # live inside inspect only
    events_path: str = ""
    seconds: Dict[str, float] = field(default_factory=dict)
    memory: Dict[str, Any] = field(default_factory=dict)
    bursts: List[Dict[str, Any]] = field(default_factory=list)
    window_mono: tuple = (0.0, 0.0)
    jax_compiles: List[tuple] = field(default_factory=list)  # (mono, s)
    cache: Dict[str, int] = field(default_factory=lambda: {
        "hits": 0, "misses": 0})
    trace: Optional[trace_mod.Trace] = None
    trace_window_s: float = 0.0
    trace_epochs: int = 0
    scratch: Dict[str, Any] = field(default_factory=dict)

    def program_events(self, cat: Optional[str] = None
                       ) -> List[Dict[str, Any]]:
        """The events the program wrote to ``--events`` so far."""
        out = []
        if os.path.isfile(self.events_path):
            with open(self.events_path) as f:
                for line in f:
                    if line.strip():
                        e = json.loads(line)
                        if cat is None or e.get("cat") == cat:
                            out.append(e)
        return out

    def in_window(self, mono: float) -> bool:
        return self.window_mono[0] <= mono <= self.window_mono[1]

    def compiles_in_window(self) -> int:
        """Programs JAX built or loaded inside the measured window."""
        return sum(self.in_window(t) for t, _ in self.jax_compiles)

    def memory_peak_bytes(self) -> Optional[int]:
        """After the window and before the reference runs: in use plus
        reserved, on the fullest chip (``harness/device.py
        peak_bytes``)."""
        return device.peak_bytes(self.memory.get("after_window", []))


def _line(obj: Dict[str, Any]) -> None:
    print(json.dumps(obj), flush=True)


def cli_argv(run: Run) -> List[str]:
    cell, tr = run.cell, run.cell.traffic
    argv = [str(a) for a in cell.config["cli"]]
    argv += ["-file", run.data.prefix, "-seed", str(run.args.seed),
             "-e", "0", "--eval-every", str(NEVER),
             "--impl", "auto", "--memory", "auto",
             # persist every program: the second run of a cell in a
             # checkout must find ALL of them in the cache
             "--cache-min-secs", "0",
             "--events", run.events_path]
    if int(tr.get("parts", 1)) > 1:
        argv += ["--parts", str(tr["parts"])]
    return argv


# ---------------------------------------------------------- the window

def _burst(run: Run, epochs: int, annotate) -> Dict[str, Any]:
    """One burst and its eval; never raises (a failure is a result)."""
    tr = run.trainer
    rec: Dict[str, Any] = {"epochs": epochs, "ok": False}
    t0 = time.perf_counter()
    try:
        with annotate("bench:train_dispatch"):
            tr.train(epochs=epochs)
        with annotate("bench:sync"):
            tr.sync()
        t1 = time.perf_counter()
        rec["train_s"] = t1 - t0
        with annotate("bench:evaluate"):
            m = tr.evaluate()
        rec["eval_s"] = time.perf_counter() - t1
        rec["train_loss"] = float(m["train_loss"])
        rec["train_acc"] = float(m["train_acc"])
        rec["ok"] = math.isfinite(rec["train_loss"])
    except Exception as e:  # noqa: BLE001 - a failed operation is counted
        rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        device.say(f"burst failed: {rec['error']}")
    return rec


def _window(run: Run, expect_s: float, annotate) -> None:
    eval_every = int(run.cell.traffic["eval_every"])
    mono0 = time.monotonic()
    end = time.perf_counter() + float(run.args.seconds)
    while True:
        rec = _burst(run, eval_every, annotate)
        run.bursts.append(rec)
        if not rec["ok"]:
            break
        expect_s = max(expect_s, rec["train_s"] + rec["eval_s"])
        if time.perf_counter() + expect_s > end:
            break
    run.window_mono = (mono0, time.monotonic())


def _traced_stretch(run: Run, trace_dir: str, annotate) -> None:
    """A short steady stretch under the profiler: ``epochs`` epochs
    closed by one sync, or ``bursts`` whole bursts with their evals."""
    import jax
    spec = run.cell.extras.get("trace", {"epochs": 2})
    eval_every = int(run.cell.traffic["eval_every"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    run.trainer.sync()
    # device operations and TraceMe spans only: the Python call tracer
    # slows the host it is measuring, and the HLO text is not read
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with annotate("bench:stretch"):
            if "bursts" in spec:
                for _ in range(int(spec["bursts"])):
                    _burst(run, eval_every, annotate)
                run.trace_epochs = int(spec["bursts"]) * eval_every
            else:
                with annotate("bench:train_dispatch"):
                    run.trainer.train(epochs=int(spec["epochs"]))
                with annotate("bench:sync"):
                    run.trainer.sync()
                run.trace_epochs = int(spec["epochs"])
        run.trace_window_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if found:
        run.scratch["xplane"] = found[0]
        run.trace = trace_mod.load(found[0])


def _own_accounting(run: Run) -> Dict[str, Any]:
    """One burst timed by the program's own ``run_epoch_loop`` (its
    ``epoch_ms`` / ``eval_ms``) and by this benchmark's clock around the
    same call: the cross-check of the two arithmetics."""
    import dataclasses
    tr = run.trainer
    eval_every = int(run.cell.traffic["eval_every"])
    keep = tr.config
    tr.config = dataclasses.replace(keep, eval_every=eval_every)
    try:
        k = eval_every - tr.epoch % eval_every
        tr.sync()
        t0 = time.perf_counter()
        hist = tr.train(epochs=k)
        wall_ms = (time.perf_counter() - t0) * 1e3
    finally:
        tr.config = keep
    if not hist:
        return {"epochs": k, "program": None}
    m = hist[-1]
    return {"epochs": k, "bench_wall_ms": wall_ms,
            "program_epoch_ms": m.get("epoch_ms"),
            "program_eval_ms": m.get("eval_ms"),
            "program_sum_ms": m["epoch_ms"] * k + m["eval_ms"]}


def placement(run: Run) -> Dict[str, Any]:
    """At parts > 1 every parameter and table spans all the devices and
    the bytes in use per chip are within 2x of each other — nothing
    quietly landed on device 0 (``chip_smoke.py placement``, copied)."""
    import jax
    tr, parts = run.trainer, len(run.devs)
    arrays = [a for a in jax.tree_util.tree_leaves(
        (tr.data.__dict__, tr.params, tr.opt_state))
        if isinstance(a, jax.Array)]
    bad = [f"{a.dtype}{list(a.shape)} on {len(a.sharding.device_set)}"
           for a in arrays if len(a.sharding.device_set) != parts]
    stats = device.memory_stats(run.devs)
    in_use = [s["bytes_in_use"] for s in stats if s]
    ok = len(arrays) > 8 and not bad
    if in_use:
        ok = ok and min(in_use) > 0 and max(in_use) <= 2 * min(in_use)
    return {"ok": ok, "arrays": len(arrays), "not_spanning": bad[:5],
            "bytes_in_use": in_use or None}


# ------------------------------------------------------------- the run

def _count_compiles(run: Run) -> None:
    """JAX's own monitoring events: every program built or loaded from
    the persistent cache (ObservedJit's and every eager op alike), and
    the cache's hits and misses."""
    import jax

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            run.jax_compiles.append((time.monotonic(), duration))

    def on_event(event: str, **_kw) -> None:
        if event == CACHE_HIT:
            run.cache["hits"] += 1
        elif event == CACHE_MISS:
            run.cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _inspect(run: Run, t_cli: float, t_proc: float):
    def inspect(trainer) -> None:
        import jax
        run.trainer = trainer
        annotate = jax.profiler.TraceAnnotation
        run.seconds["build_s"] = time.perf_counter() - t_cli
        run.memory["after_build"] = device.memory_stats(run.devs)
        t0 = time.perf_counter()
        # warm-up: exactly this cell's two programs.  The first eval is
        # also the untrained model's loss, which the trained loss must
        # fall below (the substrate is learnt in a step, so evals
        # inside the window all sit at the noise floor)
        m0 = trainer.evaluate()
        run.scratch["untrained_loss"] = float(m0["train_loss"])
        trainer.train(epochs=1)             # compiles; the loop syncs it
        t1 = time.perf_counter()
        trainer.train(epochs=1)
        trainer.sync()
        step_s = time.perf_counter() - t1
        t1 = time.perf_counter()
        trainer.evaluate()
        eval_s = time.perf_counter() - t1
        run.seconds["warmup_s"] = time.perf_counter() - t0
        run.memory["after_warmup"] = device.memory_stats(run.devs)
        run.seconds["setup_s"] = time.perf_counter() - t_proc
        expect = step_s * int(run.cell.traffic["eval_every"]) + eval_s
        _window(run, expect, annotate)
        run.memory["after_window"] = device.memory_stats(run.devs)
        # what the reference is held against: the eval program's logits
        # at the parameters the window produced
        run.scratch["logits"] = np.asarray(trainer.predict(),
                                           dtype=np.float32)
        run.scratch["params"] = {
            k: np.asarray(v, dtype=np.float32)
            for k, v in jax.device_get(trainer.params).items()}
        man = run.program_events("manifest")
        run.scratch["resolved"] = man[-1].get("resolved") if man else None
        if len(run.devs) > 1:
            run.scratch["placement"] = placement(run)
        if run.args.trace:
            run.scratch["own_accounting"] = _own_accounting(run)
            _traced_stretch(run, os.path.join(
                run.args.data_dir, "traces", run.cell.name), annotate)
            run.scratch["layer_values"] = read_layer_metrics(run)
        if run.args.probe:
            run.scratch["probe"] = run.cell.module(
                "probes", run.args.probe).probe(run)
        run.trainer = None
    return inspect


def read_layer_metrics(run: Run) -> Dict[str, Any]:
    """Each per-layer metric of this cell through its own reader,
    ``layer_metrics/<name>.py read(run)``; a reader that finds nothing
    to read returns None and the metric is left out."""
    out: Dict[str, Any] = {}
    for m in run.cell.metrics("per_layer"):
        value = run.cell.module("layer_metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = value
    return out


def check_reference(run: Run) -> Dict[str, Any]:
    import reference
    cfg, tol = run.cell.config, run.cell.extras["correct"]
    fwd = run.cell.module("references", cfg["reference"]).forward
    d = run.data
    inputs = (fwd, run.scratch["params"], d.features, d.labels, d.mask,
              d.row_ptr, d.col_idx, cfg["model"])
    import jax
    try:
        ref = reference.run(*inputs)
        where = "chip"
    except jax.errors.JaxRuntimeError as e:
        # the whole graph in float32 did not fit beside what the process
        # still holds on the chip: the same reference on the host
        device.say(f"reference on the chip failed ({str(e)[:200]}); "
                   f"running it on the host CPU")
        ref = reference.run(*inputs, on=jax.devices("cpu")[0])
        where = "host"
    out = reference.compare(run.scratch["logits"], ref["logits"])
    sys_loss = run.bursts[-1].get("train_loss") if run.bursts else None
    out["reference_ran_on"] = where
    out["reference_loss"] = ref["loss"]
    out["system_loss"] = sys_loss
    out["loss_rel_diff"] = (
        abs(sys_loss - ref["loss"]) / max(abs(ref["loss"]), 1e-9)
        if sys_loss is not None else None)
    out["ok"] = bool(
        out["finite"]
        and out["row_rel_l2_max"] <= tol["row_rel_l2_max"]
        and out["row_rel_l2_median"] <= tol["row_rel_l2_median"]
        and out["loss_rel_diff"] is not None
        and (out["loss_rel_diff"] <= tol["loss_rel"]
             or abs(sys_loss - ref["loss"]) <= tol["loss_abs"]))
    return out


def _median(xs: List[float]) -> Optional[float]:
    return statistics.median(xs) if xs else None


def run(cell, args, t_proc: float) -> int:
    try:
        devs = device.claim(cell.chips, args.rehearsal)
    except device.NoChip as e:
        device.say(str(e))
        return 2
    if not os.path.isdir(os.path.join(cells.REPO_ROOT, "roc_tpu")):
        device.say(f"the system under test (roc_tpu/) is not in "
                   f"{cells.REPO_ROOT}; nothing was run")
        return 2
    sys.path.insert(0, cells.REPO_ROOT)
    import jax
    import jaxlib
    peaks = None if args.rehearsal else cells.peaks_for(
        devs[0].device_kind)
    run_ = Run(cell=cell, args=args, devs=devs, rehearsal=args.rehearsal,
               peaks=peaks)
    _count_compiles(run_)

    from roc_tpu.obs.events import configure
    from roc_tpu.train import cli
    from roc_tpu.utils.compile_cache import resolve_cache_dir
    cache_dir = resolve_cache_dir()
    entries_before = (len(os.listdir(cache_dir))
                      if os.path.isdir(cache_dir) else 0)
    run_.memory["start"] = device.memory_stats(devs)
    run_.data = dataset.prepare(cell, args.seed, args.data_dir)
    run_.seconds.update(run_.data.seconds)
    os.makedirs(os.path.join(args.data_dir, "runs"), exist_ok=True)
    run_.events_path = os.path.join(args.data_dir, "runs",
                                    f"{cell.name}.events.jsonl")
    if os.path.exists(run_.events_path):
        os.remove(run_.events_path)          # the sink appends
    t_cli = time.perf_counter()
    try:
        rc = cli.main(cli_argv(run_), inspect=_inspect(run_, t_cli, t_proc))
    finally:
        configure(jsonl_path=None)           # close this run's sink
    if rc != 0:
        device.say(f"roc_tpu.train.cli.main exited {rc}")
        return 1
    gc.collect()                             # the trainer's cycles hold HBM
    run_.memory["before_reference"] = device.memory_stats(devs)

    t0 = time.perf_counter()
    check = check_reference(run_)
    run_.seconds["reference_s"] = time.perf_counter() - t0

    ok_bursts = [b for b in run_.bursts if b["ok"]]
    epochs = sum(b["epochs"] for b in run_.bursts)
    failed = sum(b["epochs"] + 1 for b in run_.bursts if not b["ok"])
    compiles_in_window = run_.compiles_in_window()
    observed_in_window = sum(run_.in_window(e["mono"]) for e in
                             run_.program_events("compile")
                             if "compile_s" in e)
    last_loss = ok_bursts[-1]["train_loss"] if ok_bursts else None
    learnt = (last_loss is not None
              and last_loss < run_.scratch["untrained_loss"])
    place = run_.scratch.get("placement")
    correct = bool(check["ok"] and not failed and learnt
                   and compiles_in_window == 0 and observed_in_window == 0
                   and (place is None or place["ok"]))

    def shown(value):
        """Under --rehearsal no timing or memory value is printed."""
        return None if args.rehearsal else value

    values = {
        "epoch_ms": _median([b["train_s"] / b["epochs"] * 1e3
                             for b in ok_bursts]),
        "eval_ms": _median([b["eval_s"] * 1e3 for b in ok_bursts]),
        "setup_s": run_.seconds["setup_s"],
    }
    entries_after = (len(os.listdir(cache_dir))
                     if os.path.isdir(cache_dir) else 0)
    _line({"versions": {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "numpy": np.__version__}, "rehearsal": args.rehearsal})
    _line({"plan": run_.scratch.get("resolved"),
           "graph": {"num_nodes": int(run_.data.row_ptr.shape[0] - 1),
                     "num_edges": int(run_.data.col_idx.shape[0])},
           "placement": place})
    _line({"setup_split_s": shown({
        k: run_.seconds.get(k) for k in (
            "topology_s", "features_s", "build_s", "warmup_s", "setup_s",
            "reference_s")}),
        "topology_cached": run_.data.topology_cached,
        "compile_cache": {"dir": cache_dir,
                          "entries_before": entries_before,
                          "new_entries": entries_after - entries_before,
                          **run_.cache},
        "program_compile_events": [
            {k: e.get(k) for k in ("name", "lower_s", "compile_s",
                                   "argument_bytes", "output_bytes",
                                   "temp_bytes", "peak_bytes")}
            for e in run_.program_events("compile") if "compile_s" in e]})
    _line({"samples": {"bursts": len(run_.bursts), "epochs": epochs,
                       "evals": len(run_.bursts)},
           "compiles_in_window": compiles_in_window,
           "program_compile_events_in_window": observed_in_window,
           "untrained_loss": run_.scratch["untrained_loss"],
           "last_train_loss": last_loss,
           "burst_ms": shown([
               [b.get("train_s", 0) * 1e3, b.get("eval_s", 0) * 1e3]
               for b in run_.bursts]),
           "memory": shown(run_.memory)})
    _line({"check": check})
    if "own_accounting" in run_.scratch:
        _line({"own_accounting": shown(run_.scratch["own_accounting"])})
    if "probe" in run_.scratch:
        _line({"probe": run_.scratch["probe"]})

    group = "per_layer" if args.trace else "end_to_end"
    got = run_.scratch.get("layer_values", {}) if args.trace else values
    metrics = {
        m["name"]: {"value": (got[m["name"]] if m["unit"] == "count"
                              else shown(got[m["name"]])),
                    "unit": m["unit"]}
        for m in cell.metrics(group) if got.get(m["name"]) is not None}
    dev = device.describe(devs, shown(run_.memory_peak_bytes()))
    result: Dict[str, Any] = {
        "correct": correct, "attempted": epochs + len(run_.bursts),
        "failed": failed, "metrics": metrics, "device": dev}
    if args.trace and run_.trace is not None:
        busy = trace_mod.busy_seconds(run_.trace)
        dev["busy_s"] = shown(sum(busy.values()) / len(busy)
                              if busy else None)
        dev["window_s"] = shown(run_.trace_window_s)
        result["breakdown"] = {
            "device_ops": [[n, shown(s)] for n, s
                           in trace_mod.top_ops(run_.trace)],
            "idle_gaps": [[n, shown(s)] for n, s
                          in trace_mod.idle_gaps(run_.trace)]}
    _line(result)
    return 0

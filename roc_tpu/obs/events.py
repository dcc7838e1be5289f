"""Categorized event bus: the ONE home for run diagnostics.

Every runtime decision the framework makes (impl auto-resolution,
memory plans, fuse counts, bdense occupancy) and everything the
hardware reports back (compile cost, epoch timing, stalls) flows
through :func:`emit` as a categorized event.  Two sinks:

- :class:`ConsoleSink` — preserves today's ``# ...`` stderr lines
  byte-for-byte (stdout stays a clean metrics stream; the lint
  ratchet ``scripts/lint_prints.sh`` enforces that).
- :class:`JsonlSink` — append-only structured JSONL, the machine-
  readable artifact ``python -m roc_tpu.report`` summarizes.

The module-level bus starts with a console sink only; a JSONL sink
attaches via :func:`configure` (the CLI's ``--events`` flag) or the
``ROC_TPU_EVENTS`` environment variable — inherited by child
processes, so a staged benchmark's events land in one artifact.

Deliberately jax-free and thread-safe: the stall heartbeat emits from
a watchdog thread while the main thread is blocked inside a fetch.
"""

from __future__ import annotations

import collections
import json
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional

# Canonical categories (free-form strings are accepted — a new
# category must not require touching this module):
#   manifest  run-identity event emitted at trainer setup
#   resolve   config auto-resolution (impl/fuse/attention overrides)
#   plan      memory plans, bdense occupancy, partition/ring echoes
#   compile   lowering+compile cost, XLA cost/memory introspection
#   epoch     per-eval timing, phase spans, throughput
#   stall     heartbeat "still waiting in <stage>" events
#   run       CLI lifecycle (resume, checkpoint, artifact writes)
#   analysis  roc-lint findings (python -m roc_tpu.analysis)
#   pipeline  streamed-tier / ring overlap telemetry (staging-pool
#             h2d_wait + overlap_frac, hop_compute vs hop_permute)
#   costmodel partition cost-model telemetry (core/costmodel.py):
#             split imbalance records, ridge observations, epoch-
#             boundary repartition decisions
#   programspace  compile-budget reports from the program-space
#             auditor (analysis/programspace.py): per-config program
#             counts, modeled compile cost, budget deltas
#   resilience  fault-tolerance lifecycle (roc_tpu/resilience):
#             injected faults, recovery retries, corrupt-checkpoint
#             fallbacks, preemption + emergency checkpoints, elastic
#             restores onto a different partition count
#   timeline  clock-sync handshakes and per-phase span batches the
#             cross-process trace merger consumes
#             (obs/timeline.py; python -m roc_tpu.timeline).  A batch
#             is ``kind="spans"``, ``spans=[[name, mono0, ms], ...]``;
#             a lap may carry a fourth element, a dict of per-span
#             args (the serving tier's rids; :func:`span`'s ``parent``
#             and counters), and a batch :func:`flush_spans` wrote
#             says which ``phase`` of the run it covers ("setup")
#   serve     inference-tier lifecycle (roc_tpu/serve): artifact
#             export/prewarm reports, server open/close summaries
#             (query/batch counts, latency percentiles), propagation-
#             table invalidations
#   sharding  replication-ledger / mesh-portability reports from the
#             sharding auditor (analysis/sharding_lint.py): per-rig
#             replicated bytes vs the ratcheted budget, full-width
#             sites, modeled per-device HBM per (parts, model) shape
#   checkpoint  checkpoint-v3 save lifecycle (utils/checkpoint.py +
#             resilience/async_save.py): committed async saves with
#             block/write/commit timings, superseded-snapshot drops,
#             sync-fallback decisions — the ``ckpt_*`` timeline spans
#             ride the ordinary timeline/spans batches
#   slo       SLO-engine transitions (obs/slo.py): dated burn-rate
#             breach/recovered events per objective, each carrying
#             the spec, burn multiple, alert window, and the windowed
#             value vs target — the breach also dumps the flight
#             recorder, and ``python -m roc_tpu.report --slo``
#             renders the breach windows from these records
#   protocol  protocol-audit surface from roc-lint level eight
#             (analysis/protocol_lint.py): the extracted wire
#             vocabulary per channel, transition-site index, and the
#             bounded model checker's per-model state counts and
#             invariant verdicts — ``python -m roc_tpu.report
#             --protocol`` renders the tables from these records
CATEGORIES = ("manifest", "resolve", "plan", "compile", "epoch",
              "stall", "run", "analysis", "pipeline",
              "costmodel", "programspace", "resilience", "timeline",
              "serve", "sharding", "checkpoint", "slo", "protocol")


# ---------------------------------------------------------- clock tuple
#
# Every event carries a ``(wall, monotonic, host, proc)`` clock tuple —
# ``t`` (epoch seconds, human-alignable but NTP-skewed), ``mono``
# (monotonic seconds, skew-free within a process but with an arbitrary
# per-process epoch), ``host``/``proc`` (the stream's identity).  The
# cross-process timeline merger (obs/timeline.py) aligns per-process
# monotonic clocks on the ``clock_sync`` handshake the trainers emit at
# the first-step barrier (train/trainer.py run_epoch_loop), so N
# per-process JSONL streams render on ONE time axis.  The bus stamps
# the tuple; call sites never hand-roll it (roc-lint ``event-clock``).

_HOST = socket.gethostname().split(".")[0]
_PROC: Optional[int] = None


def set_clock_identity(proc: Optional[int] = None,
                       host: Optional[str] = None) -> None:
    """Pin the process identity stamped on every event.  Called by the
    run manifest once jax knows ``process_index()``; before that the
    ``JAX_PROCESS_ID`` env var (or 0) serves."""
    global _PROC, _HOST
    if proc is not None:
        _PROC = int(proc)
    if host is not None:
        _HOST = host


def clock_identity() -> Dict[str, Any]:
    """The ``host``/``proc`` half of the clock tuple."""
    global _PROC
    if _PROC is None:
        try:
            _PROC = int(os.environ.get("JAX_PROCESS_ID", "0"))
        except ValueError:
            _PROC = 0
    return {"host": _HOST, "proc": _PROC}


def _jsonable(v: Any) -> Any:
    """Best-effort conversion to something json.dumps accepts."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple, set)):
        return [_jsonable(x) for x in v]
    try:
        import numpy as np
        if isinstance(v, np.integer):
            return int(v)
        if isinstance(v, np.floating):
            return float(v)
        if isinstance(v, np.ndarray) and v.size <= 64:
            return v.tolist()
    except ImportError:  # numpy is always present in practice
        pass
    return str(v)


class ConsoleSink:
    """``# <message>`` lines on stderr — exactly the ad-hoc diagnostic
    format the event log replaces, so existing eyes and log scrapers
    keep working."""

    def __init__(self, stream=None):
        self._stream = stream

    def write(self, record: Dict[str, Any]) -> None:
        if not record.get("console", True):
            return
        stream = self._stream if self._stream is not None else sys.stderr
        print(f"# {record['msg']}", file=stream)

    def close(self) -> None:
        pass


class JsonlSink:
    """Append-only JSONL; the handle opens lazily on first event and
    every line is flushed (a timed-out run must still leave a readable
    artifact)."""

    def __init__(self, path: str):
        self.path = path
        self._fh = None

    def write(self, record: Dict[str, Any]) -> None:
        rec = {k: _jsonable(v) for k, v in record.items()
               if k != "console"}
        if self._fh is None:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            self._fh = open(self.path, "a")
        self._fh.write(json.dumps(rec) + "\n")
        self._fh.flush()

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


class EventLog:
    """A bus fanning events out to its sinks.  Sink failures are
    swallowed after a one-time stderr note — telemetry must never take
    down the run it observes.

    Every record is stamped with the clock tuple (``t``/``mono``/
    ``host``/``proc``) and retained in a bounded ring buffer — the
    crash flight recorder :func:`dump_flight_record` writes on fatal
    paths, so a dead process's last seconds of telemetry survive even
    when no JSONL sink was configured."""

    def __init__(self, sinks: Optional[List] = None,
                 ring_events: Optional[int] = None):
        self.sinks: List = list(sinks) if sinks is not None else []
        self._lock = threading.Lock()
        self._sink_warned = False
        self.ring: collections.deque = collections.deque(
            maxlen=flight_ring_events() if ring_events is None
            else ring_events)
        # finished :func:`span` laps waiting for :func:`flush_spans`;
        # bounded, so a process that opens spans and never builds a
        # trainer (which flushes) cannot grow without limit
        self.spans: collections.deque = collections.deque(
            maxlen=SPAN_BUFFER_LAPS)

    def emit(self, cat: str, msg: str, console: bool = True,
             **fields: Any) -> Dict[str, Any]:
        record = {"t": round(time.time(), 3),
                  "mono": round(time.monotonic(), 6),
                  **clock_identity(),
                  "cat": cat, "msg": msg,
                  "console": console, **fields}
        with self._lock:
            self.ring.append(record)
            for sink in self.sinks:
                try:
                    # the bus lock IS the sink serializer: concurrent
                    # emitters writing the same JSONL handle unlocked
                    # would tear lines; the hold is bounded (one
                    # flushed line): roc-lint: ok=blocking-under-lock
                    sink.write(record)
                except Exception as e:  # noqa: BLE001 - never raise
                    if not self._sink_warned:
                        self._sink_warned = True
                        print(f"# event sink {type(sink).__name__} "
                              f"failed: {e!r} (further failures "
                              f"silent)", file=sys.stderr)
        return record

    def flush_spans(self, phase: str) -> Optional[Dict[str, Any]]:
        """Emit the buffered :func:`span` laps as ONE ``timeline`` /
        ``spans`` event and empty the buffer; None when it held
        nothing."""
        with self._lock:
            laps = list(self.spans)
            self.spans.clear()
        if not laps:
            return None
        return self.emit("timeline", f"spans: {len(laps)} laps ({phase})",
                         console=False, kind="spans", phase=phase,
                         spans=laps)

    def add_sink(self, sink) -> None:
        with self._lock:
            self.sinks.append(sink)

    def jsonl_path(self) -> Optional[str]:
        for sink in self.sinks:
            if isinstance(sink, JsonlSink):
                return sink.path
        return None

    def close(self) -> None:
        with self._lock:
            for sink in self.sinks:
                try:
                    sink.close()
                except Exception:  # noqa: BLE001
                    pass


_BUS: Optional[EventLog] = None
_BUS_LOCK = threading.Lock()


def get_bus() -> EventLog:
    """The process-global bus, created on first use: a console sink,
    plus a JSONL sink when ``ROC_TPU_EVENTS`` is set (child processes
    and multi-host workers inherit the artifact path via env)."""
    global _BUS
    with _BUS_LOCK:
        if _BUS is None:
            _BUS = EventLog([ConsoleSink()])
            env_path = os.environ.get("ROC_TPU_EVENTS")
            if env_path:
                _BUS.add_sink(JsonlSink(env_path))
        return _BUS


def configure(jsonl_path: Optional[str] = None,
              console: bool = True) -> EventLog:
    """(Re)build the global bus.  ``jsonl_path`` attaches the JSONL
    sink; ``console=False`` drops the stderr lines (library embedding
    that wants pure-JSONL telemetry)."""
    global _BUS
    with _BUS_LOCK:
        if _BUS is not None:
            _BUS.close()
        sinks: List = [ConsoleSink()] if console else []
        if jsonl_path:
            sinks.append(JsonlSink(jsonl_path))
        _BUS = EventLog(sinks)
        return _BUS


def emit(cat: str, msg: str, console: bool = True,
         **fields: Any) -> Dict[str, Any]:
    """Emit on the global bus.  ``console=False`` keeps an event out
    of the stderr stream (it still lands in the JSONL artifact) — the
    call-site analog of today's ``if config.verbose:`` gates."""
    return get_bus().emit(cat, msg, console=console, **fields)


# ---------------------------------------------------------- phase spans

# laps the buffer holds between two flushes (a trainer's set-up writes
# a few dozen)
SPAN_BUFFER_LAPS = 4096

_SPAN_STACK = threading.local()


class span(dict):
    """``with span(name, **counters) as s:`` records one lap of a named
    phase on the bus's clock (``time.monotonic()``, the ``mono`` every
    event carries) into the bus's span buffer, as the four-element lap
    ``[name, mono0, ms, {"parent": <enclosing span's name or None>,
    **counters}]`` the ``timeline`` category carries.  ``s`` is the
    dict of counters: the body may add to it (``s["h2d_bytes"] +=
    a.nbytes``; a counter not given yet reads 0), and after the block
    ``s.ms`` holds the lap's duration.  Nesting is per thread.  A body
    that raises is still recorded.  :func:`flush_spans` writes the
    buffer out.

    No switch turns it off: a lap costs two clock reads and a list
    append.  It adds no device sync: a span around ``jnp.asarray`` is
    the host's time inside the call.  The laps are not entered as
    ``jax.profiler.TraceAnnotation``s — the phases this names (a
    trainer's set-up) run before any profiler session exists;
    ``utils/profiling.py EpochTimer.annotate`` stays the
    ``--profile-dir`` path for the epoch loop's phases."""

    def __init__(self, name: str, **counters: Any):
        super().__init__(counters)
        self.name = name
        self.ms: Optional[float] = None

    def __missing__(self, key: str) -> int:
        return 0

    def __enter__(self) -> "span":
        stack = _SPAN_STACK.__dict__.setdefault("names", [])
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._mono0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.monotonic() - self._mono0) * 1e3
        _SPAN_STACK.names.pop()
        bus = get_bus()
        with bus._lock:
            bus.spans.append([self.name, round(self._mono0, 6),
                              round(self.ms, 3),
                              {"parent": self._parent, **self}])


def flush_spans(phase: str) -> Optional[Dict[str, Any]]:
    """Write the :class:`span` laps recorded since the last flush as
    one ``timeline`` event (``kind="spans"``, ``phase=phase``,
    ``console=False``) on the global bus.  Without a JSONL sink it
    reaches the flight-recorder ring only."""
    return get_bus().flush_spans(phase)


# ------------------------------------------------ crash flight recorder
#
# The JSONL sink flushes per line, but a process that dies WITHOUT a
# sink configured — or whose interesting telemetry was console-only —
# takes its last seconds of events with it (the r01-r05 probes died
# exactly like that).  The bus therefore keeps a bounded ring of recent
# records, and the fatal paths (preemption guard, stall watchdog,
# fault-injection sites about to SIGKILL, the unhandled-exception hook)
# dump it to a dated ``flightrecord_*.json`` for the post-mortem.

# ring capacity (events, not bytes): ~30 s of a chatty run
FLIGHT_RING_EVENTS = 256


def flight_ring_events() -> int:
    try:
        return int(os.environ.get("ROC_TPU_FLIGHT_EVENTS",
                                  FLIGHT_RING_EVENTS))
    except ValueError:
        return FLIGHT_RING_EVENTS


def flight_record_dir() -> str:
    """Where dumps land: ``ROC_TPU_FLIGHT_DIR``, else next to the JSONL
    events artifact, else the cwd."""
    env = os.environ.get("ROC_TPU_FLIGHT_DIR")
    if env:
        return env
    jl = get_bus().jsonl_path()
    if jl:
        return os.path.dirname(os.path.abspath(jl)) or "."
    return "."


def dump_flight_record(reason: str,
                       path: Optional[str] = None) -> Optional[str]:
    """Write the ring buffer to a dated flight-record JSON; returns the
    path, or None on failure (a dump must never mask the failure that
    triggered it).  Filename carries the date, pid, and a slug of the
    reason so multiple dumps of one incident coexist."""
    bus = get_bus()
    try:
        ident = clock_identity()
        if path is None:
            slug = "".join(c if c.isalnum() else "-"
                           for c in reason)[:40].strip("-")
            name = (f"flightrecord_"
                    f"{time.strftime('%Y%m%d-%H%M%S')}_"
                    f"p{ident['proc']}_pid{os.getpid()}_{slug}.json")
            path = os.path.join(flight_record_dir(), name)
        with bus._lock:
            events = [
                {k: _jsonable(v) for k, v in r.items() if k != "console"}
                for r in bus.ring]
        payload = {"reason": reason,
                   "t": round(time.time(), 3),
                   "mono": round(time.monotonic(), 6),
                   "pid": os.getpid(), **ident,
                   "n_events": len(events), "events": events}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001 - never mask the trigger
        try:
            print(f"# flight-record dump failed: {e!r}",
                  file=sys.stderr)
        except OSError:
            pass
        return None
    try:
        print(f"# flight record ({reason}): {path}", file=sys.stderr)
    except OSError:
        pass
    return path


_EXCEPTHOOK_INSTALLED = False


def install_excepthook() -> None:
    """Chain a flight-record dump onto ``sys.excepthook`` so an
    unhandled exception leaves the last telemetry window behind.
    Idempotent; the previous hook always runs."""
    global _EXCEPTHOOK_INSTALLED
    if _EXCEPTHOOK_INSTALLED:
        return
    _EXCEPTHOOK_INSTALLED = True
    prev = sys.excepthook

    def hook(exc_type, exc, tb):
        if not issubclass(exc_type, (KeyboardInterrupt, SystemExit)):
            dump_flight_record(f"unhandled {exc_type.__name__}")
        prev(exc_type, exc, tb)

    sys.excepthook = hook

"""Rule-driven AST lint over the source tree.

Generalizes the original ``scripts/lint_prints.sh`` heredoc (whose
stdout-print rule migrated here verbatim) into a registry of rules,
each scoped to the modules whose invariants it guards.  Suppression is
per-line and self-documenting: a trailing ``# roc-lint: ok`` (any
rule) or ``# roc-lint: ok=rule-a,rule-b`` on the flagged line — or the
line above it — accepts the finding at the call site, with the comment
text carrying the why.  jax-free by design: the AST layer must run in
milliseconds with no backend.

Adding a rule: subclass :class:`AstRule`, set ``name``/``why``,
implement ``select`` (which repo-relative paths it lints) and
``check`` (yield :class:`Finding`), and append an instance to
:data:`RULES`.  Give every finding a line number and a stable ``key``
if the message embeds location-dependent text.
"""

from __future__ import annotations

import ast
import pathlib
from typing import Iterable, List, Optional

from .findings import Finding


def pragma_ok(lines: List[str], lineno: Optional[int],
              rule: str) -> bool:
    """True when the flagged line (or the line above — decorators,
    wrapped calls) carries a ``# roc-lint: ok`` pragma covering
    ``rule``."""
    if lineno is None:
        return False
    for ln in (lineno, lineno - 1):
        if not 1 <= ln <= len(lines):
            continue
        text = lines[ln - 1]
        mark = "roc-lint: ok"
        pos = text.find(mark)
        if pos < 0:
            continue
        rest = text[pos + len(mark):]
        if not rest.startswith("="):
            return True          # bare pragma: every rule
        names = rest[1:].split()[0] if rest[1:].split() else ""
        if rule in [r.strip() for r in names.split(",")]:
            return True
    return False


class AstRule:
    name = "abstract"
    why = ""

    def select(self, relpath: str) -> bool:
        raise NotImplementedError

    def check(self, tree: ast.AST, relpath: str) -> Iterable[Finding]:
        raise NotImplementedError


def _is_name(node: ast.AST, name: str) -> bool:
    return isinstance(node, ast.Name) and node.id == name


def _is_attr(node: ast.AST, attr: str,
             base: Optional[str] = None) -> bool:
    """``<base>.<attr>`` (any base when ``base`` is None)."""
    return (isinstance(node, ast.Attribute) and node.attr == attr
            and (base is None or _is_name(node.value, base)))


class StdoutPrintRule(AstRule):
    """Bare ``print()`` to stdout — stdout belongs to the metrics
    stream (the ``[INFER]`` lines); diagnostics go through
    ``roc_tpu.obs.events.emit`` or ``file=sys.stderr``.  Allowed
    surfaces: the console event sink, the report CLI, and this
    package's own CLI — places whose stdout IS their product."""

    name = "stdout-print"
    why = ("stdout is a clean metrics stream; route diagnostics "
           "through roc_tpu.obs.events.emit (or file=sys.stderr for "
           "pre-bus error paths)")
    ALLOW_FILES = {"roc_tpu/obs/events.py", "roc_tpu/report.py",
                   "roc_tpu/analysis/__main__.py",
                   # the prewarm CLI's stdout IS its product (one
                   # machine-readable JSON report line per config)
                   "roc_tpu/prewarm.py",
                   # same for the timeline merger: its stdout is the
                   # report
                   "roc_tpu/obs/timeline.py", "roc_tpu/timeline.py",
                   # the serve export CLI prints one JSON report line
                   # (error paths go to stderr like every CLI here)
                   "roc_tpu/serve/export.py", "roc_tpu/export.py"}

    def select(self, relpath: str) -> bool:
        return relpath not in self.ALLOW_FILES

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _is_name(node.func, "print")):
                continue
            if any(kw.arg == "file" for kw in node.keywords):
                continue    # explicit stream (stderr error paths)
            if (len(node.args) == 1
                    and isinstance(node.args[0], ast.Call)
                    and _is_name(node.args[0].func, "format_metrics")):
                continue    # the sanctioned [INFER] metrics line
            yield Finding(self.name, relpath,
                          "bare print() to stdout", line=node.lineno,
                          key=f"print@{node.lineno}")


class HostSyncHotPathRule(AstRule):
    """Implicit device→host syncs in hot-path modules: a single
    ``jax.device_get`` / ``.item()`` / ``float(arr)`` inside the
    aggregation/kernel/streaming code serializes the dispatch pipeline
    every step — exactly the stall class the async epoch loop exists
    to avoid.  ``float()`` of a plain name or literal (config scalars)
    is not flagged; computed expressions are."""

    name = "host-sync-hot-path"
    why = ("hot-path modules must stay fetch-free: host syncs "
           "serialize the async dispatch pipeline")
    # serve/ is scoped in as a whole: a device_get/.item() inside the
    # request loop serializes every queued microbatch behind one
    # query's fetch — exactly the latency bug class this tier will
    # grow.  The ONE sanctioned fetch (the result itself) carries a
    # pragma at the call site (serve/predictor.py).
    HOT_PREFIXES = ("roc_tpu/ops/", "roc_tpu/serve/")
    HOT_FILES = {"roc_tpu/core/streaming.py"}

    def select(self, relpath: str) -> bool:
        return (relpath.startswith(self.HOT_PREFIXES)
                or relpath in self.HOT_FILES)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_attr(node.func, "device_get") or \
                    _is_name(node.func, "device_get"):
                yield Finding(self.name, relpath,
                              "jax.device_get in a hot-path module",
                              line=node.lineno,
                              key=f"device_get@{node.lineno}")
            elif (_is_attr(node.func, "item") and not node.args
                    and not node.keywords):
                yield Finding(self.name, relpath,
                              ".item() in a hot-path module "
                              "(implicit device fetch)",
                              line=node.lineno,
                              key=f"item@{node.lineno}")
            elif (_is_name(node.func, "float") and len(node.args) == 1
                    and not isinstance(node.args[0],
                                       (ast.Constant, ast.Name))):
                yield Finding(self.name, relpath,
                              "float(<expr>) in a hot-path module "
                              "(implicit device fetch on arrays)",
                              line=node.lineno,
                              key=f"float@{node.lineno}")


class SyncH2dInLoopRule(AstRule):
    """Synchronous host→device staging inside a Python loop: a
    ``jax.device_put`` / ``np.ascontiguousarray`` in a ``for``/
    ``while`` body puts the host copy + H2D transfer on the critical
    path of every iteration — exactly the latency-serial pattern the
    staging pool (``core/streaming.py StagingPool``) exists to hide.
    Route block staging through the pool (``_stage_block`` is the one
    sanctioned call site, and it lives outside any loop); genuinely
    cold loops suppress with ``# roc-lint: ok=sync-h2d-in-loop``."""

    name = "sync-h2d-in-loop"
    why = ("a per-iteration device_put/ascontiguousarray serializes "
           "the transfer behind compute; stage through "
           "core/streaming.StagingPool so block k+1's copy runs "
           "under block k's work")
    HOT_PREFIXES = ("roc_tpu/ops/",)
    HOT_FILES = {"roc_tpu/core/streaming.py"}

    def select(self, relpath: str) -> bool:
        return (relpath.startswith(self.HOT_PREFIXES)
                or relpath in self.HOT_FILES)

    LOOP_NODES = (ast.For, ast.AsyncFor, ast.While, ast.ListComp,
                  ast.SetComp, ast.DictComp, ast.GeneratorExp)

    def check(self, tree, relpath):
        seen = set()
        for loop in ast.walk(tree):
            if not isinstance(loop, self.LOOP_NODES):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                if _is_attr(node.func, "device_put") or \
                        _is_name(node.func, "device_put"):
                    what = "device_put"
                elif _is_attr(node.func, "ascontiguousarray") or \
                        _is_name(node.func, "ascontiguousarray"):
                    what = "ascontiguousarray"
                else:
                    continue
                key = f"{what}@{node.lineno}"
                if key in seen:     # nested loops walk twice
                    continue
                seen.add(key)
                yield Finding(self.name, relpath,
                              f"{what} inside a loop body — "
                              "synchronous H2D on the critical path "
                              "(stage through StagingPool)",
                              line=node.lineno, key=key)


class BareJitRule(AstRule):
    """``jax.jit`` in the trainer/parallel layers that bypasses
    ``ObservedJit`` — such steps compile invisibly: no lower/compile
    wall time, no cost/memory introspection, no modeled-vs-actual HBM
    check.  Allowed only lexically inside an ``ObservedJit(...)`` call
    (the ``jitfn=jax.jit(...)`` form for pre-wrapped shard_map
    steps)."""

    name = "bare-jit"
    why = ("steps must compile through ObservedJit so cost/memory "
           "introspection and the modeled-vs-actual HBM check see "
           "them")
    PREFIXES = ("roc_tpu/train/", "roc_tpu/parallel/")

    def select(self, relpath: str) -> bool:
        return relpath.startswith(self.PREFIXES)

    def check(self, tree, relpath):
        observed_spans = []
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and (_is_name(node.func, "ObservedJit")
                         or _is_attr(node.func, "ObservedJit"))):
                observed_spans.append(
                    (node.lineno, node.end_lineno or node.lineno))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _is_attr(node.func, "jit", base="jax")):
                continue
            if any(lo <= node.lineno <= hi
                   for lo, hi in observed_spans):
                continue    # ObservedJit(jitfn=jax.jit(...)) form
            yield Finding(self.name, relpath,
                          "bare jax.jit bypasses ObservedJit",
                          line=node.lineno,
                          key=f"jit@{node.lineno}")


class SwallowedExceptionRule(AstRule):
    """Silently swallowed exceptions in the recovery/streaming/
    checkpoint paths: a bare ``except:`` (any body — it eats
    KeyboardInterrupt and SystemExit too), or any handler whose body
    is only ``pass``/``...``.  These are exactly the modules whose job
    is to SURFACE faults — a swallow here converts a diagnosable
    failure (corrupt checkpoint, dead stager, half-written file) into
    silent data loss, the reference's ``exit(1)`` failure model with
    the exit removed.  Genuinely-benign swallows (best-effort cleanup)
    suppress with ``# roc-lint: ok=swallowed-exception`` and a reason,
    like every rule."""

    name = "swallowed-exception"
    why = ("recovery/streaming/checkpoint paths must surface "
           "failures: route them to the resilience event stream or "
           "re-raise, or pragma the line with the why")
    PREFIXES = ("roc_tpu/resilience/",)
    FILES = {"roc_tpu/utils/checkpoint.py",
             "roc_tpu/utils/resilience.py",
             "roc_tpu/core/streaming.py"}

    def select(self, relpath: str) -> bool:
        return (relpath.startswith(self.PREFIXES)
                or relpath in self.FILES)

    @staticmethod
    def _body_is_noop(body) -> bool:
        return all(isinstance(s, ast.Pass)
                   or (isinstance(s, ast.Expr)
                       and isinstance(s.value, ast.Constant)
                       and s.value.value is Ellipsis)
                   for s in body)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield Finding(self.name, relpath,
                              "bare except: swallows KeyboardInterrupt"
                              "/SystemExit too — name the exception",
                              line=node.lineno,
                              key=f"bare-except@{node.lineno}")
            elif self._body_is_noop(node.body):
                yield Finding(self.name, relpath,
                              "exception handler body is only pass — "
                              "the failure vanishes without a trace",
                              line=node.lineno,
                              key=f"except-pass@{node.lineno}")


class EventClockRule(AstRule):
    """Events must go through the bus helper that stamps the clock
    tuple (``obs/events.py emit``): the cross-process timeline merger
    aligns per-process streams on the ``(t, mono, host, proc)`` stamps
    the bus owns, so (a) no call site may hand-pass any of those
    reserved fields to ``emit`` (a caller-supplied ``t=``/``proc=``
    would silently mis-lane the record in the merged trace), and
    (b) no module outside the bus may hand-roll an event record (a
    dict literal carrying both ``"cat"`` and ``"msg"`` keys) — a
    hand-rolled dict written straight to a JSONL file has no clock
    tuple and falls off the merged time axis."""

    name = "event-clock"
    why = ("the bus stamps the (wall, monotonic, host, proc) clock "
           "tuple; hand-stamped or hand-rolled event records break "
           "the cross-process timeline alignment")
    RESERVED = {"t", "mono", "host", "proc"}
    ALLOW_FILES = {"roc_tpu/obs/events.py"}

    def select(self, relpath: str) -> bool:
        return relpath not in self.ALLOW_FILES

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and (
                    _is_name(node.func, "emit")
                    or _is_attr(node.func, "emit")):
                bad = sorted(kw.arg for kw in node.keywords
                             if kw.arg in self.RESERVED)
                if bad:
                    yield Finding(
                        self.name, relpath,
                        f"emit() hand-passes reserved clock field(s) "
                        f"{bad} — the bus stamps the clock tuple",
                        line=node.lineno,
                        key=f"emit-clock@{node.lineno}")
            elif isinstance(node, ast.Dict):
                keys = {k.value for k in node.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)}
                if {"cat", "msg"} <= keys:
                    yield Finding(
                        self.name, relpath,
                        "hand-rolled event record (dict literal with "
                        "'cat' and 'msg' keys) — construct events "
                        "through obs.events.emit so the clock tuple "
                        "is stamped",
                        line=node.lineno,
                        key=f"event-dict@{node.lineno}")


class MetricAdhocRule(AstRule):
    """Serving/training hot paths must record metrics through the
    streaming registry (``obs/metrics_registry.py``), not ad-hoc
    instance state: a hand-rolled ``self._n_foo += 1`` counter has no
    window and no snapshot, and an unbounded ``*_ms``/``*_lat`` list
    grows without limit AND costs an O(n) sort at every quantile read
    — exactly the failure modes the registry's O(1) counters and
    log-bucket histograms exist to close.  Flags (a) ``+=``/``-=``
    augmented assignment onto a ``_n_*`` attribute and (b)
    ``.append(...)`` onto an attribute ending ``_ms``/``_lat``.
    Sanctioned buffers (the trainer's timeline span laps) carry a
    ``# roc-lint: ok=metric-adhoc`` pragma saying why."""

    name = "metric-adhoc"
    why = ("hot-path counters/latency samples belong in the metrics "
           "registry (windowed, O(1), snapshot-able) — ad-hoc "
           "attributes have no window and unbounded lists leak")
    PREFIXES = ("roc_tpu/serve/",)
    FILES = {"roc_tpu/train/trainer.py"}

    def select(self, relpath: str) -> bool:
        return (relpath.startswith(self.PREFIXES)
                or relpath in self.FILES)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if (isinstance(node, ast.AugAssign)
                    and isinstance(node.target, ast.Attribute)
                    and node.target.attr.startswith("_n_")):
                yield Finding(
                    self.name, relpath,
                    f"ad-hoc counter '{node.target.attr} "
                    f"{type(node.op).__name__}=' — use a registry "
                    f"Counter (windowed, O(1) inc)",
                    line=node.lineno,
                    key=f"adhoc-counter@{node.lineno}")
            elif (isinstance(node, ast.Call)
                  and _is_attr(node.func, "append")
                  and isinstance(node.func.value, ast.Attribute)
                  and node.func.value.attr.endswith(("_ms", "_lat"))):
                yield Finding(
                    self.name, relpath,
                    f"ad-hoc latency list "
                    f"'{node.func.value.attr}.append' — use a "
                    f"registry Histogram (log-bucket, bounded, "
                    f"windowed quantiles)",
                    line=node.lineno,
                    key=f"adhoc-latency@{node.lineno}")


class DequantHotPathRule(AstRule):
    """Materializing a full fp32 copy of a quantized serving table
    inside ``roc_tpu/serve/``: the whole point of int8/fp8 tables
    (``serve/quant.py``) is that the ``[V, F]`` buffer never widens —
    the serve programs gather the bucket's rows and dequantize
    IN-REGISTER.  An ``.astype(float32)`` (or
    ``asarray(..., dtype=float32)`` / ``float32(...)`` cast) applied
    to a table/stage-named array undoes the capacity win in one line
    and doubles+ the replica's memory right where it is scarcest.
    Sanctioned sites — host-side build/load paths and rows-only
    refresh slices — carry a ``# roc-lint: ok=dequant-hot-path``
    pragma saying why they are not the hot path."""

    name = "dequant-hot-path"
    why = ("serve/ must dequantize gathered rows in-register — a "
           "full fp32 copy of a [V, F] table forfeits the quantized "
           "capacity win; pragma host-side build/refresh sites")

    def select(self, relpath: str) -> bool:
        return relpath.startswith("roc_tpu/serve/")

    @staticmethod
    def _is_f32(node: ast.AST) -> bool:
        return (_is_attr(node, "float32") or _is_name(node, "float32")
                or (isinstance(node, ast.Constant)
                    and node.value == "float32"))

    @staticmethod
    def _tableish(expr: ast.AST) -> bool:
        for n in ast.walk(expr):
            ident = (n.id if isinstance(n, ast.Name)
                     else n.attr if isinstance(n, ast.Attribute)
                     else None)
            if ident and ("table" in ident.lower()
                          or "stage" in ident.lower()):
                return True
        return False

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "astype"
                    and node.args and self._is_f32(node.args[0])
                    and self._tableish(f.value)):
                yield Finding(
                    self.name, relpath,
                    "full fp32 .astype on a table-shaped array — "
                    "dequantize gathered rows in-register instead",
                    line=node.lineno, key=f"astype@{node.lineno}")
            elif (isinstance(f, ast.Attribute) and f.attr == "asarray"
                    and node.args and self._tableish(node.args[0])
                    and any(kw.arg == "dtype" and self._is_f32(kw.value)
                            for kw in node.keywords)):
                yield Finding(
                    self.name, relpath,
                    "asarray(<table>, dtype=float32) materializes a "
                    "full fp32 table copy",
                    line=node.lineno, key=f"asarray@{node.lineno}")
            elif (self._is_f32(f) and node.args
                    and self._tableish(node.args[0])):
                yield Finding(
                    self.name, relpath,
                    "float32(<table>) cast materializes a full fp32 "
                    "table copy",
                    line=node.lineno, key=f"cast@{node.lineno}")


RULES: List[AstRule] = [StdoutPrintRule(), HostSyncHotPathRule(),
                        SyncH2dInLoopRule(), BareJitRule(),
                        SwallowedExceptionRule(), EventClockRule(),
                        MetricAdhocRule(), DequantHotPathRule()]


def run_ast_lint(root: str,
                 select: Optional[List[str]] = None) -> List[Finding]:
    """Run the AST rules over ``<root>/roc_tpu/**/*.py``.  ``select``
    restricts to the named rules (unknown names raise — a typo must
    not silently skip a gate)."""
    rules = RULES
    if select is not None:
        from .concurrency_lint import CONCURRENCY_RULES
        from .driver import is_trace_rule   # lazy: no import cycle
        from .protocol_lint import PROTOCOL_RULES
        known = {r.name for r in RULES}
        bad = [s for s in select
               if s not in known and not is_trace_rule(s)
               and s not in CONCURRENCY_RULES
               and s not in PROTOCOL_RULES]
        if bad:
            raise ValueError(f"unknown lint rule(s): {bad}; "
                             f"AST rules: {sorted(known)}")
        rules = [r for r in RULES if r.name in select]
    findings: List[Finding] = []
    base = pathlib.Path(root)
    for path in sorted(base.glob("roc_tpu/**/*.py")):
        rel = path.relative_to(base).as_posix()
        applicable = [r for r in rules if r.select(rel)]
        if not applicable:
            continue
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src, filename=rel)
        for rule in applicable:
            for f in rule.check(tree, rel):
                if not pragma_ok(lines, f.line, rule.name):
                    findings.append(f)
    return findings

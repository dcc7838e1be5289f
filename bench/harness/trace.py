"""From a profiler trace (``.xplane.pb``) to numbers: device busy and
idle, the operations that took most time, the longest idle gaps and
what the host was doing in them, and collective time hidden or exposed.

The trace is read with nothing but JAX (``jax.profiler.ProfileData``).
Where the device's operations are found:

* on an accelerator, each chip is a plane ``/device:TPU:<n>``; its
  operations are the events of the line ``XLA Ops`` (named by their
  whole HLO text, ``%fusion.7 = bf16[...] fusion(...)``), and the line
  ``Async XLA Ops`` holds one span from each ``*-start`` to its
  ``*-done`` (copies, and across chips the collectives in flight);
* on the CPU backend (this benchmark's tests) there is no device plane:
  the operations are the host-plane events that carry an ``hlo_op`` stat,
  and their ``device_ordinal`` stat says which virtual device ran them.

Host spans are the ``jax.profiler.TraceAnnotation`` events of the host
plane; the benchmark's own are named ``bench:<what>``.

Operations on one line may nest (a ``while`` holds its body).  Busy time
is the union of intervals, so nesting does not count twice; an
operation's *self* time is its duration less its children's, and only
operations with no children count as "running" when collectives are
set against compute.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:([A-Za-z]+):(\d+)")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# lines of a device plane that are not single operations
NOT_OPS_LINES = {"Steps", "XLA Modules", "XLA TraceMe", ASYNC_LINE,
                 "Framework Name Scope", "Framework Ops", "Source code",
                 "TC Overlay"}
# "%fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion(...)": name, result type
HLO_TEXT = re.compile(r"^%?([^\s=]+) = (\([^)]*\)|[a-z0-9]+\[[^\]]*\])")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|psum|ppermute")
HOST_SPAN_PREFIX = "bench:"

Interval = Tuple[int, int]


@dataclass
class Op:
    name: str
    start: int                     # ns
    end: int                       # ns
    category: str = ""
    self_ns: int = 0
    leaf: bool = True

    @property
    def collective(self) -> bool:
        text = f"{self.name.split(' = ')[0]} {self.category}"
        return bool(COLLECTIVE.search(text.lower().replace("_", "-")))

    @property
    def kind(self) -> str:
        """What the breakdown groups by: the instruction's name without
        its number, and its result type without the layout —
        ``fusion bf16[1048576,256]``.  A name that is not HLO text
        stays as it is."""
        m = HLO_TEXT.match(re.sub(r"\{[^}]*\}", "", self.name[:400]))
        if not m:
            return re.sub(r"[.\d]+$", "", self.name) or self.name
        return f"{re.sub(r'[.0-9]+$', '', m.group(1))} {m.group(2)}"[:96]


@dataclass
class Trace:
    chips: Dict[int, List[Op]] = field(default_factory=dict)
    in_flight: Dict[int, List[Op]] = field(default_factory=dict)
    host_spans: List[Op] = field(default_factory=list)


# ------------------------------------------------------------ intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def length(merged: Iterable[Interval]) -> int:
    return sum(hi - lo for lo, hi in merged)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of merged ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def mark_nesting(ops: List[Op]) -> None:
    """Fill ``self_ns`` and ``leaf`` for the operations of ONE line."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        op.self_ns, op.leaf = op.end - op.start, True
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
            stack[-1].leaf = False
        stack.append(op)


# -------------------------------------------------------------- reading

def _stats(event) -> Dict[str, Any]:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    tr = Trace()
    device_planes = [(p, DEVICE_PLANE.match(p.name)) for p in planes]
    device_planes = [(p, int(m.group(2))) for p, m in device_planes if m]
    for plane, chip in device_planes:
        lines = list(plane.lines)
        chosen = [ln for ln in lines if ln.name == OPS_LINE] or [
            ln for ln in lines if ln.name not in NOT_OPS_LINES]
        for line in chosen:
            ops = [Op(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns),
                      str(_stats(e).get("hlo_category", "")))
                   for e in line.events]
            mark_nesting(ops)
            tr.chips.setdefault(chip, []).extend(ops)
        for line in lines:
            if line.name == ASYNC_LINE:
                tr.in_flight.setdefault(chip, []).extend(
                    Op(e.name, int(e.start_ns),
                       int(e.start_ns + e.duration_ns))
                    for e in line.events)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            per_chip: Dict[int, List[Op]] = {}
            for e in line.events:
                if e.name.startswith(HOST_SPAN_PREFIX):
                    tr.host_spans.append(Op(
                        e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns)))
                elif not device_planes and not e.name.startswith("end:"):
                    st = _stats(e)
                    if "hlo_op" in st:
                        per_chip.setdefault(
                            int(st.get("device_ordinal", 0)), []).append(
                            Op(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns)))
            for chip, ops in per_chip.items():
                mark_nesting(ops)
                tr.chips.setdefault(chip, []).extend(ops)
    return tr


# ------------------------------------------------------------ reduction

def busy_ns(ops: List[Op]) -> int:
    return length(union((o.start, o.end) for o in ops))


def busy_seconds(tr: Trace) -> Dict[int, float]:
    return {chip: busy_ns(ops) / 1e9 for chip, ops in tr.chips.items()}


def idle_share(tr: Trace, window_s: float) -> Optional[float]:
    """1 - busy / window on the WORST chip (the most idle one)."""
    busy = busy_seconds(tr)
    if not busy or window_s <= 0:
        return None
    return max(0.0, 1.0 - min(busy.values()) / window_s)


def top_ops(tr: Trace, n: int = 10) -> List[List[Any]]:
    """``[["<kind> x<calls>", seconds], ...]``: self time by kind of
    operation (:attr:`Op.kind`), averaged over the chips, largest
    first."""
    if not tr.chips:
        return []
    total: Dict[str, List[int]] = {}
    for ops in tr.chips.values():
        for o in ops:
            row = total.setdefault(o.kind, [0, 0])
            row[0] += o.self_ns
            row[1] += 1
    rows = sorted(total.items(), key=lambda kv: -kv[1][0])[:n]
    k = len(tr.chips)
    return [[f"{kind} x{calls // k}", ns / 1e9 / k]
            for kind, (ns, calls) in rows]


def _covering(spans: List[Op], lo: int, hi: int) -> str:
    """The innermost host span that covers the middle of ``[lo, hi)``."""
    mid = (lo + hi) // 2
    best: Optional[Op] = None
    for s in spans:
        if s.start <= mid < s.end and (
                best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best else "(no host span)"


def idle_gaps(tr: Trace, n: int = 10) -> List[List[Any]]:
    """``[["<host span> x<count>", seconds], ...]``: on the most idle
    chip, the stretches in which no operation ran, summed by the host
    span that covers each (what the host was doing meanwhile), largest
    first.  The stretch before the first and after the last operation
    counts only where a ``bench:stretch`` span on the same clock bounds
    it."""
    if not tr.chips:
        return []
    chip = min(tr.chips, key=lambda c: busy_ns(tr.chips[c]))
    merged = union((o.start, o.end) for o in tr.chips[chip])
    if not merged:
        return []
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    whole = HOST_SPAN_PREFIX + "stretch"
    stretch = [s for s in tr.host_spans if s.name == whole
               and s.start <= merged[0][0] and merged[-1][1] <= s.end]
    if stretch:
        gaps += [(stretch[0].start, merged[0][0]),
                 (merged[-1][1], stretch[0].end)]
    inner = [s for s in tr.host_spans if s.name != whole]
    total: Dict[str, List[int]] = {}
    for lo, hi in gaps:
        if hi > lo:
            row = total.setdefault(_covering(inner, lo, hi), [0, 0])
            row[0] += hi - lo
            row[1] += 1
    rows = sorted(total.items(), key=lambda kv: -kv[1][0])[:n]
    return [[f"{name} x{cnt}", ns / 1e9] for name, (ns, cnt) in rows]


def collectives(tr: Trace) -> Optional[Dict[str, float]]:
    """On the chip that spends most time in collectives: the seconds in
    which one ran or was in flight (the union of the collective
    operations and of the collective ``start``..``done`` spans), and the
    seconds of them during which no other operation ran on that chip.
    None when the trace holds none."""
    best: Optional[Dict[str, float]] = None
    for chip, ops in tr.chips.items():
        coll = [o for o in ops if o.leaf and o.collective]
        coll += [o for o in tr.in_flight.get(chip, []) if o.collective]
        if not coll:
            continue
        other = union((o.start, o.end) for o in ops
                      if o.leaf and not o.collective)
        merged = union((o.start, o.end) for o in coll)
        row = {"chip": chip, "collective_s": length(merged) / 1e9,
               "exposed_s": length(subtract(merged, other)) / 1e9,
               "calls": len(coll)}
        if best is None or row["collective_s"] > best["collective_s"]:
            best = row
    return best

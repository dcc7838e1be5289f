"""Set-up by phase, from the spans the program records of its own
build: shared by ``setup_load_s``, ``setup_resolve_s``,
``setup_tables_s``, ``setup_upload_s`` and ``setup_unspanned_share``.

The program (``roc_tpu/obs/events.py span`` / ``flush_spans``) writes,
as the last act of a trainer's constructor, one ``timeline`` event with
``kind == "spans"`` and ``phase == "setup"``: ``spans`` is a list of
laps ``[name, mono0, ms, {"parent": <name or None>, **counters}]`` on
the clock of every event's ``mono``.  ``cli.main`` opens the first
(``setup.load``) and the constructor the rest; a name may be entered
many times.  The reduction here:

* a lap's children are the laps that name it as ``parent`` and lie
  inside its interval; its self time is its duration less the union of
  theirs (choosing-metrics §4);
* per ``(name, parent)``: how often it was entered, total and self
  seconds, and its numeric counters summed; per ``table=`` / ``what=``
  label of a ``setup.tables`` / ``setup.upload`` lap the same, so the
  line says which table the time went to;
* ``top_s``: the laps with no parent, summed — what the spans cover of
  the benchmark's ``build_s`` (``perf_counter`` around ``cli.main`` up
  to the ``inspect`` hand-over).

One diagnostic line goes to standard output before the result line:
``{"setup_spans": {"rows": [[name, parent, n, total_s, self_s,
counters], ...], "by_label": [[name, label, n, total_s, counters],
...], "top_s", "build_s", "h2d_gb_per_s"}}`` — ``h2d_gb_per_s`` is the
``h2d_bytes`` of the ``setup.upload`` laps over their seconds: the
host's side of the hand-over, not the link's rate (the call returns
before the device holds the bytes).  Under ``--rehearsal`` every
timing is null and the counts stay.  A program that flushes no such
batch (a parent commit) gives nothing to read: no line, no metric.
"""

import json

UPLOAD = "setup.upload"
# the program rounds a lap's start and length to the microsecond
ROUNDING_S = 5e-6


def _covered(spans):
    """Length of the union of ``(lo, hi)`` intervals."""
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(spans):
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return total


def _numeric(args):
    """The counters of a lap's args (a flag counts as 0 or 1); its
    ``parent`` and labels are not numbers."""
    return {k: v for k, v in args.items() if isinstance(v, (int, float))}


def _add(into, counters):
    for k, v in counters.items():
        into[k] = into.get(k, 0) + v


def reduce_laps(laps):
    """The reduction, on one batch's ``spans``; seconds throughout."""
    laps = [(name, t0, ms / 1e3, args) for name, t0, ms, args in laps]
    rows, labels = {}, {}
    top_s = 0.0
    for i, (name, t0, dur, args) in enumerate(laps):
        parent = args.get("parent")
        inside = [(c0, c0 + cd) for j, (_, c0, cd, ca) in enumerate(laps)
                  if j != i and ca.get("parent") == name
                  and t0 - ROUNDING_S <= c0
                  and c0 + cd <= t0 + dur + ROUNDING_S]
        counters = _numeric(args)
        row = rows.setdefault((name, parent), [0, 0.0, 0.0, {}])
        row[0] += 1
        row[1] += dur
        row[2] += dur - min(_covered(inside), dur)
        _add(row[3], counters)
        label = args.get("table", args.get("what"))
        if label is not None:
            lab = labels.setdefault((name, str(label)), [0, 0.0, {}])
            lab[0] += 1
            lab[1] += dur
            _add(lab[2], counters)
        if parent is None:
            top_s += dur
    return {"rows": rows, "labels": labels, "top_s": top_s}


def total_s(got, names, self_time=False):
    """Seconds under the spans ``names``, whatever their parent: their
    whole durations, or their self time."""
    return sum(row[2 if self_time else 1]
               for (name, _), row in got["rows"].items() if name in names)


def measure(run):
    if "setup_spans" not in run.scratch:
        run.scratch["setup_spans"] = _measure(run)
    return run.scratch["setup_spans"]


def _measure(run):
    batches = [e for e in run.program_events("timeline")
               if e.get("kind") == "spans" and e.get("phase") == "setup"]
    if not batches:
        return None
    # the trainer this run was handed is the last one the CLI built
    got = reduce_laps(batches[-1].get("spans") or [])
    got["build_s"] = run.seconds.get("build_s")
    upload_s = total_s(got, (UPLOAD,))
    h2d = sum(row[3].get("h2d_bytes", 0)
              for (name, _), row in got["rows"].items() if name == UPLOAD)

    def shown(v):
        return None if run.rehearsal else v

    print(json.dumps({"setup_spans": {
        "rows": [[name, parent, n, shown(tot), shown(own), counters]
                 for (name, parent), (n, tot, own, counters)
                 in got["rows"].items()],
        "by_label": [[name, label, n, shown(tot), counters]
                     for (name, label), (n, tot, counters)
                     in got["labels"].items()],
        "top_s": shown(got["top_s"]), "build_s": shown(got["build_s"]),
        "h2d_gb_per_s": shown(h2d / upload_s / 1e9 if upload_s else None),
    }}), flush=True)
    return got


def phase_s(run, names, self_time=False):
    """A phase metric: seconds under ``names``; None when the program
    flushed no set-up batch."""
    got = measure(run)
    if got is None:
        return None
    return total_s(got, names, self_time)

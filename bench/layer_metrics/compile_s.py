"""``compile_s`` (``compile`` layer, s): the sum of ``lower_s + compile_s``
over the run's ``compile`` events (``obs/compile_watch.ObservedJit``,
read from ``--events``).  Cold it is XLA's compile, warm the persistent
cache's load."""


def read(run):
    got = [e["lower_s"] + e["compile_s"]
           for e in run.program_events("compile") if "compile_s" in e]
    return float(sum(got)) if got else None

"""``peak_hbm_gib`` (``device`` layer, GiB): peak device memory on the
fullest chip, after the window and before the reference runs: the
allocator's ``peak_bytes_in_use`` plus its ``peak_bytes_reserved`` (the
scratch region a loaded program holds), both from
``device.memory_stats()``.  PR 22's probe (``probes/memory_headroom.py``)
showed that the first figure alone misses most of what a step occupies:
see ``harness/device.py peak_bytes`` and PERF.md sections 5 and 6."""


def read(run):
    peak = run.memory_peak_bytes()
    return None if peak is None else peak / 2**30

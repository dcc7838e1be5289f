"""``plan_miss_gib`` (``entry`` layer, GiB): how far the memory plan's
estimate for the configuration it resolved lies from what the chip held,
``|estimate - peak|`` — the distance that decides whether ``--memory
auto`` turns remat, the ring halo or host streaming on at the right
size.  The estimate is the program's own: ``memory_plan.est_bytes`` in
the ``resolved`` block of its ``manifest`` event (the ``plan`` line),
the sum of the plan's components for the plan that runs.  The peak is
``peak_hbm_gib``'s: in use plus reserved on the fullest chip, after the
window.  A program whose manifest carries no ``memory_plan`` (a parent
commit) gives nothing to read."""


def read(run):
    plan = (run.scratch.get("resolved") or {}).get("memory_plan") or {}
    peak = run.memory_peak_bytes()
    if plan.get("est_bytes") is None or peak is None:
        return None
    return abs(float(plan["est_bytes"]) - peak) / 2**30

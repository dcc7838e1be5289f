"""``setup_upload_s`` (``host_table_build`` layer, s): self seconds of
the program's ``setup.upload`` (the host's time inside every
host-to-device hand-over of set-up), ``setup.params`` (parameter and
Adam initialisation and placement) and ``setup.steps`` (the step
objects' construction; nothing compiles there) spans.  Source: the
set-up span batch the program flushes — see ``_setup_spans.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_setup_spans").phase_s(
        run, ("setup.upload", "setup.params", "setup.steps"),
        self_time=True)

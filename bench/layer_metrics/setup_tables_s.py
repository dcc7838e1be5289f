"""``setup_tables_s`` (``host_table_build`` layer, s): self seconds of
the program's ``setup.tables`` (every table's numpy build) and
``setup.partition`` (the vertex partitioner) spans.  Source: the set-up
span batch the program flushes — see ``_setup_spans.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_setup_spans").phase_s(
        run, ("setup.tables", "setup.partition"), self_time=True)

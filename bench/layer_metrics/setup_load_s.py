"""``setup_load_s`` (``host_table_build`` layer, s): seconds of set-up
under the program's ``setup.load`` (the dataset's four files),
``setup.typed`` (a typed graph's kinds and relations read off it) and
``setup.reorder`` (``--reorder``) spans.  Source: the set-up span batch
the program flushes — see ``_setup_spans.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_setup_spans").phase_s(
        run, ("setup.load", "setup.typed", "setup.reorder"))

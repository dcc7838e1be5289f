"""``setup_resolve_s`` (``entry`` layer, s): seconds of set-up the
program spends deciding and describing, as opposed to building — its
``setup.resolve`` (fuse, ``auto``'s probe, the memory plan, relations),
``setup.symmetry`` (``check_symmetric``, which has a row of its own on
the ``setup_spans`` line) and ``setup.manifest`` spans.  Source: the
set-up span batch the program flushes — see ``_setup_spans.py``."""


def read(run):
    return run.cell.module("layer_metrics", "_setup_spans").phase_s(
        run, ("setup.resolve", "setup.symmetry", "setup.manifest"))

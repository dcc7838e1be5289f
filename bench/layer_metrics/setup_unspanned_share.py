"""``setup_unspanned_share`` (``host_table_build`` layer, %): the share
of the benchmark's ``build_s`` (``cli.main`` from its entry to the
``inspect`` hand-over) that no top-level ``setup.`` span of the program
covers — the tracing's own coverage, so that the four phase metrics
cannot shrink in silence.  Source: the set-up span batch the program
flushes — see ``_setup_spans.py``."""


def read(run):
    got = run.cell.module("layer_metrics", "_setup_spans").measure(run)
    if got is None or not got["build_s"]:
        return None
    return 100.0 * (got["build_s"] - got["top_s"]) / got["build_s"]

#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its files
are found by name (``harness/cells.py``) and its traffic mix names the
driver that runs it (``drivers/<name>.py``).  Standard output ends with
one JSON object holding ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and ``breakdown`` with ``--trace 1``);
diagnostic objects go on earlier lines.  Without an accelerator, or
with fewer chips than the cell asks for, nothing is run, no result is
printed and the exit code is 2.  See ``bench/README.md``.
"""

import time

T_PROCESS = time.perf_counter()    # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from harness import cells, device  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell: the name of an entry of `workloads`")
    ap.add_argument("--seed", type=int, default=0,
                    help="features, mask, parameter init and dropout "
                         "stream (the topology has the traffic file's "
                         "graph_seed)")
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: the "
                         "benchmark file's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="0: end-to-end metrics, profiler off; 1: the "
                         "per-layer metrics, with a short traced stretch")
    ap.add_argument("--benchmark", default=os.path.join(
        cells.REPO_ROOT, "BENCHMARK.json"),
        help="the table of cells (default: BENCHMARK.json at the root)")
    ap.add_argument("--data-dir", default=os.path.join(
        cells.REPO_ROOT, "data", "bench"),
        help="generated datasets, event logs and traces (ignored by git)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="the benchmark's own tests: CPU backend, same "
                         "control flow, every timing and memory value "
                         "null")
    ap.add_argument("--probe", default=None,
                    help="run probes/<name>.py on the live trainer after "
                         "the measurements and print what it finds")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = cells.load_cell(args.benchmark, args.workload)
        if args.seconds is None:
            args.seconds = float(cell.benchmark["run_seconds"])
        driver = cell.module("drivers", cell.traffic["driver"])
    except cells.CellError as e:
        device.say(str(e))
        return 2
    return driver.run(cell, args, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())

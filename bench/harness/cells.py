"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one
substrate, one driver or one per-layer metric is a file of its own:

    <root>/workloads/<cell>.json       tolerances, traced stretch
    <root>/traffic/<mix>.json          the job: driver, parts, cadence
    <config file named in BENCHMARK.json>   sizes, flags, reference
    <root>/substrates/<name>.py        graph + feature generator
    <root>/drivers/<name>.py           how a traffic mix is run
    <root>/references/<name>.py        a configuration's plain forward
    <root>/layer_metrics/<name>.py     one per-layer metric's reader

``<root>`` is each directory in the benchmark file's ``paths``, in
order, then this harness's own directory — so a later PR adds a cell by
adding files and entries, and a test can point ``--benchmark`` at a
small table of its own.  Nothing here branches on a name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


class CellError(Exception):
    """The benchmark's own files are missing or do not fit together."""


def read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"{path}: {e}") from e


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]         # the configuration file
    traffic: Dict[str, Any]        # the traffic-mix file
    extras: Dict[str, Any]         # the cell's own file
    benchmark: Dict[str, Any]      # the whole BENCHMARK.json
    roots: List[str] = field(default_factory=list)

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """This cell's metrics of ``end_to_end`` or ``per_layer``: those
        with no ``workloads`` key, or with this cell listed in it."""
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def find(self, kind: str, name: str, suffix: str) -> str:
        for root in self.roots:
            path = os.path.join(root, kind, name + suffix)
            if os.path.isfile(path):
                return path
        raise CellError(f"no {kind}/{name}{suffix} under {self.roots}")

    def module(self, kind: str, name: str):
        """Import ``<root>/<kind>/<name>.py`` under a name of its own
        (two roots may both hold a ``drivers/`` directory)."""
        path = self.find(kind, name, ".py")
        mod_name = "bench_" + "".join(
            c if c.isalnum() else "_" for c in f"{kind}_{name}")
        loaded = sys.modules.get(mod_name)
        if loaded is not None and getattr(loaded, "__file__", None) == path:
            return loaded
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod      # dataclasses look the module up
        spec.loader.exec_module(mod)
        return mod


def _one(items: List[Dict[str, Any]], name: str, what: str,
         path: str) -> Dict[str, Any]:
    got = [x for x in items if x.get("name") == name]
    if len(got) != 1:
        known = sorted(x.get("name", "?") for x in items)
        raise CellError(f"{path}: {len(got)} {what} named {name!r} "
                        f"(known: {known})")
    return got[0]


def load_cell(benchmark_path: str, workload: str) -> Cell:
    bench = read_json(benchmark_path)
    base = os.path.dirname(os.path.abspath(benchmark_path))
    roots = [os.path.normpath(os.path.join(base, p))
             for p in bench.get("paths", [])]
    if BENCH_DIR not in roots:
        roots.append(BENCH_DIR)
    entry = _one(bench["workloads"], workload, "workload", benchmark_path)
    cfg_entry = _one(bench["configs"], entry["config"], "config",
                     benchmark_path)
    cell = Cell(name=workload, chips=int(entry["chips"]),
                config=read_json(os.path.join(base, cfg_entry["file"])),
                traffic={}, extras={}, benchmark=bench, roots=roots)
    cell.traffic = read_json(cell.find("traffic", entry["traffic"],
                                       ".json"))
    cell.extras = read_json(cell.find("workloads", workload, ".json"))
    return cell


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise CellError(f"bench/peaks.json has no row for device kind "
                        f"{device_kind!r} (known: "
                        f"{sorted(k for k in table if k != 'source')})")
    return table[device_kind]

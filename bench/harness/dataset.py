"""A cell's dataset on disk, in the program's own input format.

The CLI reads ``-file <prefix>``: ``<prefix>.add_self_edge.lux`` (u32
V, u64 E, V u64 inclusive row ends, E u32 sources), ``.feats.bin``
(float32 row-major), ``.label`` (one class index a line) and ``.mask``
(Train/Val/Test/None a line) — ``roc_tpu/core/graph.py load_dataset``.
The format is the program's interface; the generator is the
benchmark's (``substrates/``).

The topology (and the labels, which the homophilous edges depend on) is
drawn from the traffic file's ``graph_seed`` and kept from run to run:
the ``.lux`` file is the cache, in a directory named by the substrate,
the shape, the seed and a hash of the substrate's source.  Features and
mask are drawn from ``--seed`` and rewritten by every run.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
import time
from dataclasses import dataclass
from typing import Any, Dict

import numpy as np

_MASK_NAMES = np.array(["None", "Train", "Val", "Test"])   # MASK_* order


@dataclass
class Prepared:
    prefix: str
    row_ptr: np.ndarray
    col_idx: np.ndarray
    labels: np.ndarray
    features: np.ndarray
    mask: np.ndarray
    topology_cached: bool
    seconds: Dict[str, float]


def _replace_into(path: str, write) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        write(f)
    os.replace(tmp, path)


def write_lux(path: str, row_ptr: np.ndarray, col_idx: np.ndarray) -> None:
    def write(f):
        f.write(struct.pack("<IQ", row_ptr.shape[0] - 1,
                            col_idx.shape[0]))
        row_ptr[1:].astype("<u8").tofile(f)
        col_idx.astype("<u4").tofile(f)
    _replace_into(path, write)


def read_lux(path: str):
    with open(path, "rb") as f:
        num_nodes, num_edges = struct.unpack("<IQ", f.read(12))
        ends = np.fromfile(f, dtype="<u8", count=num_nodes)
        col = np.fromfile(f, dtype="<u4", count=num_edges)
    if ends.shape[0] != num_nodes or col.shape[0] != num_edges:
        raise IOError(f"{path}: truncated")
    row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)
    row_ptr[1:] = ends.astype(np.int64)
    return row_ptr, col.astype(np.int32)


def _write_lines(path: str, lines: np.ndarray) -> None:
    _replace_into(path, lambda f: f.write(
        ("\n".join(lines.tolist()) + "\n").encode()))


def topology_key(substrate_path: str, shape: Dict[str, Any],
                 substrate: Dict[str, Any]) -> str:
    h = hashlib.sha256()
    with open(substrate_path, "rb") as f:
        h.update(f.read())
    h.update(json.dumps([shape, substrate], sort_keys=True).encode())
    return h.hexdigest()[:12]


def prepare(cell, seed: int, data_dir: str) -> Prepared:
    """Generate (or find) the cell's topology, draw this run's features
    and mask, and leave all four files at the returned prefix."""
    shape = cell.config["graph"]
    sub = cell.traffic["substrate"]
    gen = cell.module("substrates", sub["name"])
    key = topology_key(cell.find("substrates", sub["name"], ".py"),
                       shape, sub)
    d = os.path.join(data_dir, "graphs",
                     f"{sub['name']}-v{shape['num_nodes']}"
                     f"-e{shape['num_edges']}-g{sub['graph_seed']}-{key}")
    os.makedirs(d, exist_ok=True)
    prefix = os.path.join(d, "graph")
    lux, lab_npy = prefix + ".add_self_edge.lux", prefix + ".labels.npy"
    done = prefix + ".topology.json"       # written last: the commit mark
    secs: Dict[str, float] = {}
    t0 = time.perf_counter()
    cached = os.path.isfile(done)
    if cached:
        row_ptr, col_idx = read_lux(lux)
        labels = np.load(lab_npy)
    else:
        topo = gen.make_topology(
            shape["num_nodes"], shape["num_edges"], shape["num_classes"],
            sub["graph_seed"], **sub.get("params", {}))
        row_ptr, col_idx, labels = (topo["row_ptr"], topo["col_idx"],
                                    topo["labels"])
        write_lux(lux, row_ptr, col_idx)
        np.save(lab_npy, labels)
        _write_lines(prefix + ".label", labels.astype(str))
        _replace_into(done, lambda f: f.write(json.dumps({
            "num_nodes": int(row_ptr.shape[0] - 1),
            "num_edges": int(col_idx.shape[0]), "key": key}).encode()))
    secs["topology_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    drawn = gen.make_features(labels, shape["in_dim"],
                              shape["num_classes"], seed,
                              **sub.get("params", {}))
    feats, mask = drawn["features"], drawn["mask"]
    _replace_into(prefix + ".feats.bin",
                  lambda f: feats.astype(np.float32, copy=False).tofile(f))
    _write_lines(prefix + ".mask", _MASK_NAMES[mask])
    secs["features_s"] = time.perf_counter() - t0
    return Prepared(prefix=prefix, row_ptr=row_ptr, col_idx=col_idx,
                    labels=labels, features=feats, mask=mask,
                    topology_cached=cached, seconds=secs)

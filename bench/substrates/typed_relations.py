"""Substrate ``typed_relations``: a typed graph as ONE symmetric,
self-edged CSR whose vertex kinds are contiguous id ranges, at a public
heterogeneous dataset's kind counts and per-relation edge counts.

``node_types`` gives the kinds' vertex counts in id order; ``relations``
lists ``[src kind, dst kind, edges]``, one entry per relation the
dataset stores.  A bipartite entry is drawn as that many distinct
``(u, v)`` pairs and stored in both directions (the public scripts add
every reverse relation); an entry within one kind as that many distinct
unordered pairs, stored both ways (a symmetrised citation relation);
every vertex stores its self edge, which the program's file format
carries and a typed model drops.  So the file holds ``2 * sum(edges) +
V`` edges and the program reads one relation per ordered pair of kinds
that occurs.

Both endpoints of every pair are drawn in proportion to a per-vertex
lognormal weight (sigma 1.25, the value ``skewed_homophilous`` uses):
prolific authors, hub fields, a few large institutions.  A uniform
bipartite graph flatters the width-8 layout.  Pairs within kind 0 are
homophilous on the label (the second endpoint is drawn from the
first's class with probability ``homophily``); kind 0 alone carries
labels, features and the mask.

Two draws, two seeds, as in ``skewed_homophilous``: :func:`make_topology`
from the traffic file's ``graph_seed`` (the harness caches it on disk),
:func:`make_features` from ``--seed``.  A copy-and-extend of that
substrate's pair drawing (the benchmark owns its generators).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

MASK_NONE, MASK_TRAIN, MASK_VAL, MASK_TEST = 0, 1, 2, 3   # gnn.h:98-103

DEFAULTS = {"degree_sigma": 1.25, "homophily": 0.8,
            "feature_mean_scale": 2.0, "train_frac": 0.85,
            "val_frac": 0.09}

_MAX_ROUNDS = 16
_DRAW_CHUNK = 1 << 23


def _pick(rng, cw: np.ndarray, n: int, lo=None, hi=None) -> np.ndarray:
    """``n`` positions drawn in proportion to the weights whose
    cumulative sum is ``cw``, each inside its own ``[lo, hi)`` stretch
    of cumulative weight where given."""
    u = rng.random(n)
    if lo is None:
        u *= cw[-1]
    else:
        u *= hi - lo
        u += lo
    return np.minimum(np.searchsorted(cw, u, side="right"),
                      cw.shape[0] - 1)


def _sorted_unique(keys: np.ndarray) -> np.ndarray:
    if keys.shape[0] == 0:
        return keys
    keys.sort()
    keep = np.empty(keys.shape[0], dtype=bool)
    keep[0] = True
    np.not_equal(keys[1:], keys[:-1], out=keep[1:])
    return keys[keep]


def _distinct_pairs(want: int, draw, rng) -> np.ndarray:
    """Exactly ``want`` distinct int64 pair keys from ``draw(n)``:
    repeated draws steer the distinct count past the target and the
    surplus is thinned at random."""
    keys = np.zeros(0, dtype=np.int64)
    rate = 1.0
    for _ in range(_MAX_ROUNDS):
        short = want - keys.shape[0]
        if short <= 0:
            break
        n = int(short / max(rate, 0.05) * 1.02) + 64
        before = keys.shape[0]
        got = [keys]
        for start in range(0, n, _DRAW_CHUNK):
            got.append(draw(min(_DRAW_CHUNK, n - start)))
        keys = _sorted_unique(np.concatenate(got))
        rate = max((keys.shape[0] - before) / n, 1e-3)
    if keys.shape[0] < want:
        raise ValueError(f"{want} distinct pairs could not be drawn "
                         f"({keys.shape[0]} after {_MAX_ROUNDS} rounds)")
    if keys.shape[0] > want:
        keep = np.ones(keys.shape[0], dtype=bool)
        keep[rng.choice(keys.shape[0], keys.shape[0] - want,
                        replace=False)] = False
        keys = keys[keep]
    return keys


def make_topology(num_nodes: int, num_edges: int, num_classes: int,
                  graph_seed: int, **params) -> Dict[str, np.ndarray]:
    """``{"row_ptr": int64 [V+1], "col_idx": int32 [E], "labels": int32
    [V]}``: the destination-major union CSR — symmetric, every self
    edge, ``E == 2 * sum(relation edges) + V == num_edges`` exactly —
    and the labels (kind 0's classes; 0 for every other kind)."""
    p = {**DEFAULTS, **params}
    node_types = [int(n) for n in p["node_types"]]
    V = int(num_nodes)
    off = np.concatenate([[0], np.cumsum(node_types)]).astype(np.int64)
    if int(off[-1]) != V:
        raise ValueError(f"node_types {node_types} count {int(off[-1])} "
                         f"vertices, the configuration says {V}")
    stored = 2 * sum(int(e) for _, _, e in p["relations"]) + V
    if stored != int(num_edges):
        raise ValueError(f"the relations store {stored} edges with "
                         f"reverses and self edges, the configuration "
                         f"says {num_edges}")
    rng = np.random.default_rng([int(graph_seed), 0])
    labels = np.zeros(V, dtype=np.int32)
    labels[:node_types[0]] = rng.integers(0, num_classes,
                                          size=node_types[0])
    # one lognormal weight a vertex, on every side it appears on
    cw = [np.cumsum(rng.lognormal(0.0, p["degree_sigma"], size=n))
          for n in node_types]
    # kind 0 in class order, for the homophilous second endpoint
    lab0 = labels[:node_types[0]]
    order = np.argsort(lab0, kind="stable")
    w0 = np.diff(cw[0], prepend=0.0)
    cw_cls = np.cumsum(w0[order])
    ends = np.cumsum(np.bincount(lab0, minlength=num_classes))
    cls_hi = cw_cls[np.maximum(ends - 1, 0)]
    cls_lo = np.concatenate([[0.0], cls_hi[:-1]])

    keys = []
    for s, d, edges in p["relations"]:
        s, d, edges = int(s), int(d), int(edges)

        def draw(n, s=s, d=d):
            a = _pick(rng, cw[s], n)
            if s != d:
                b = _pick(rng, cw[d], n)
                return ((a + off[s]) << 32) | (b + off[d])
            if s == 0:
                same = rng.random(n) < p["homophily"]
                lab = lab0[a]
                b = order[_pick(rng, cw_cls, n,
                                np.where(same, cls_lo[lab], 0.0),
                                np.where(same, cls_hi[lab], cw_cls[-1]))]
            else:
                b = _pick(rng, cw[s], n)
            keep = a != b
            a, b = a[keep] + off[s], b[keep] + off[s]
            return (np.minimum(a, b) << 32) | np.maximum(a, b)

        keys.append(_distinct_pairs(edges, draw, rng))
    keys = np.concatenate(keys)
    lo, hi = keys >> 32, keys & 0xFFFFFFFF
    del keys
    diag = np.arange(V, dtype=np.int64)
    # dst-major: key = dst << 32 | src, both directions and the diagonal
    full = np.concatenate([(lo << 32) | hi, (hi << 32) | lo,
                           (diag << 32) | diag])
    del lo, hi
    full.sort()
    col_idx = (full & 0xFFFFFFFF).astype(np.int32)
    full >>= 32
    row_ptr = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(np.bincount(full, minlength=V), out=row_ptr[1:])
    return {"row_ptr": row_ptr, "col_idx": col_idx, "labels": labels}


def make_features(labels: np.ndarray, in_dim: int, num_classes: int,
                  seed: int, **params) -> Dict[str, np.ndarray]:
    """``{"features": float32 [V, in_dim], "mask": int32 [V]}``: kind 0
    gets class means plus unit noise and a train/val/test split; every
    other kind zeros (its input is a trainable table) and ``None``."""
    p = {**DEFAULTS, **params}
    V, n0 = labels.shape[0], int(p["node_types"][0])
    rng = np.random.default_rng([int(seed), 1])
    means = (rng.standard_normal((num_classes, in_dim), dtype=np.float32)
             * np.float32(p["feature_mean_scale"]))
    feats = np.zeros((V, in_dim), dtype=np.float32)
    feats[:n0] = rng.standard_normal((n0, in_dim), dtype=np.float32)
    feats[:n0] += means[labels[:n0]]
    split = rng.random(n0)
    mask = np.full(V, MASK_NONE, dtype=np.int32)
    t, v = p["train_frac"], p["train_frac"] + p["val_frac"]
    mask[:n0] = np.where(split < t, MASK_TRAIN,
                         np.where(split < v, MASK_VAL, MASK_TEST))
    return {"features": feats, "mask": mask}

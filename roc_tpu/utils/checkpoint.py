"""Checkpoint / resume with integrity + identity validation.

The reference has **no** persistence at all (SURVEY §5: weights are
never saved; the only cache is the feature-CSV binary).  This module
grew through three formats:

- **v1** — a bare ``.npz`` of the flattened state (no validation).
- **v2** (resilience PR) — one atomic ``.npz`` with a JSON
  ``__header__`` carrying per-array CRC32s and a two-half config
  fingerprint.  Exactly right while params/opt state are fully
  replicated — and wrong the moment the 2-D ``(parts, model)`` mesh
  shards parameters: one process cannot (and must not) serialize
  arrays it only holds a shard of.
- **v3** (this PR) — a checkpoint is a DIRECTORY:

  .. code-block:: text

      <path>/                      (e.g. ck.40/)
        shard_00000.npz            per-PROCESS shard file: only the
        shard_00001.npz            array pieces this process owns
        MANIFEST.json              the commit record (process 0 only)

  Each process writes only the shards it owns (``replica_id == 0``
  dedup over the array's global sharding — a fully replicated array
  is owned by process 0 alone, which is the degenerate
  sharded→replicated path today's 1-D mesh exercises).  Every shard
  member carries the PR-14 sharding-spec vocabulary in the shard
  header (global shape, per-dim mesh-axis spec, piece index ranges),
  so restore can gather ANY saved (P, mesh) layout onto any restore
  layout: the loader reassembles full host arrays from the recorded
  piece indices and the restoring trainer re-places them through its
  own partition machinery (elastic restore).

  **Two-phase commit**: every shard lands via tmp → fsync → rename;
  then (after a cross-process barrier when more than one process owns
  shards) process 0 publishes ``MANIFEST.json`` — shard list, sizes,
  whole-file CRC32s, epoch, fingerprint — itself via tmp → fsync →
  rename + a directory fsync.  A checkpoint without a committed
  manifest is INVISIBLE to the rotation's ``restore_latest``, so
  death at any byte offset of the save leaves either the previous
  complete checkpoint or the new complete one — never a torn read.
  Restore validates the manifest, every listed shard's existence +
  file CRC, every member CRC against the shard header, and full
  piece coverage of every array before anything touches the trainer.

v1/v2 single-file checkpoints still load, each with a loud
``resilience`` event (v1: no validation possible; v2: legacy format,
migrated to v3 on the next save).

Both trainers share this module; the async saver
(:mod:`roc_tpu.resilience.async_save`) snapshots on the step path via
:func:`snapshot_trainer` and runs :func:`write_snapshot` (CRC + write
+ commit) on its background thread.
"""

from __future__ import annotations

import io
import json
import os
import tempfile
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ell import check_stored_aggr_impl
from ..obs.events import emit
from ..train.optimizer import AdamState

CHECKPOINT_VERSION = 3
_HEADER_KEY = "__header__"
MANIFEST_NAME = "MANIFEST.json"


class CheckpointCorrupt(RuntimeError):
    """A checkpoint failed integrity (CRC32/structure/coverage) or
    strict config-fingerprint validation.  Distinct from load errors
    of a missing file: the rotation layer catches this and falls back
    to the previous checkpoint."""


def _flatten(tree: Any, prefix: str) -> Dict[str, np.ndarray]:
    out = {}
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in leaves:
        key = prefix + jax.tree_util.keystr(path)
        out[key] = np.asarray(leaf)
    return out


def _unflatten(tree_template: Any, data, prefix: str, path: str) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(tree_template)
    paths = [p for p, _ in jax.tree_util.tree_leaves_with_path(
        tree_template)]
    new_leaves = []
    for kpath, tmpl in zip(paths, leaves):
        key = prefix + jax.tree_util.keystr(kpath)
        if key not in data:
            raise CheckpointCorrupt(
                f"{path}: missing array {key!r} (template/"
                f"checkpoint mismatch)")
        arr = data[key]
        if arr.shape != tuple(tmpl.shape):
            raise CheckpointCorrupt(
                f"{path}: shape mismatch at {key}: "
                f"{arr.shape} vs {tmpl.shape}")
        new_leaves.append(jnp.asarray(arr, dtype=tmpl.dtype))
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _fsync_dir(d: str) -> None:
    """Make a completed rename durable: the rename itself is not on
    disk until the DIRECTORY entry is (process death alone never
    needed this; power loss did)."""
    dfd = os.open(d, os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def params_signature(params: Any) -> str:
    """The param-tree identity hash (paths + shapes + dtypes) — the
    ``params_sig`` member of the strict fingerprint half.  ONE
    derivation shared by :func:`trainer_fingerprint` and the serve
    export (``roc_tpu/serve/export.py`` embeds it in the serving
    manifest), so a checkpoint and the artifact exported from it can
    never disagree about what the weights are."""
    import hashlib
    sigs = [f"{jax.tree_util.keystr(p)}:"
            f"{tuple(int(d) for d in leaf.shape)}:{leaf.dtype}"
            for p, leaf in
            jax.tree_util.tree_leaves_with_path(params)]
    return hashlib.sha1("|".join(sigs).encode()).hexdigest()[:16]


def trainer_fingerprint(trainer) -> Dict[str, Any]:
    """The saving/restoring trainer's identity, in two halves:

    - ``strict`` — what a checkpoint can never survive changing: the
      param-tree signature (paths + shapes + dtypes), the param/
      compute dtypes, and the dataset's V/E.  A mismatch is a
      :class:`CheckpointCorrupt` at restore.
    - ``elastic`` — what an elastic restart may legally change: the
      partition count and its quantized plan shapes
      (``quantize_plan_shapes`` output, carried on the
      PartitionedGraph) plus the resolved residency knobs.  A
      mismatch restores anyway (the v3 loader gathers the saved
      layout back to full host arrays, which are partition-
      independent) and leaves a dated resilience event.
    """
    strict: Dict[str, Any] = {
        "params_sig": params_signature(trainer.params)}
    cfg = getattr(trainer, "config", None)
    if cfg is not None:
        strict["dtype"] = str(jnp.dtype(cfg.dtype))
        strict["compute_dtype"] = (
            None if cfg.compute_dtype is None
            else str(jnp.dtype(cfg.compute_dtype)))
    ds = getattr(trainer, "_fp_dataset", None)
    if ds:
        strict["dataset"] = {k: int(v) for k, v in ds.items()}
    pg = getattr(trainer, "pg", None)
    elastic: Dict[str, Any] = {
        "num_parts": int(pg.num_parts) if pg is not None else 1,
        "part_nodes": int(pg.part_nodes) if pg is not None else None,
        "part_edges": int(pg.part_edges) if pg is not None else None}
    if cfg is not None:
        elastic.update(aggr_impl=cfg.aggr_impl, halo=cfg.halo,
                       features=cfg.features,
                       mesh=getattr(cfg, "mesh", "auto"))
    return {"strict": strict, "elastic": elastic}


# --------------------------------------------------- v3: host snapshot

def shard_file_name(proc: int) -> str:
    return f"shard_{int(proc):05d}.npz"


@dataclass
class _Piece:
    """One contiguous block of one array, owned by THIS process.
    ``index`` is the per-dim ``[lo, hi)`` range in the global array
    (None = the full array)."""
    member: str
    key: str
    index: Optional[List[List[int]]]
    data: np.ndarray


@dataclass
class Snapshot:
    """A host-side state snapshot, fully decoupled from the trainer
    and from jax: :func:`write_snapshot` (CRC + write + commit) can
    run it on the async saver thread while training dispatches the
    next epoch."""
    epoch: int
    proc: int
    writer_procs: List[int]
    pieces: List[_Piece]
    arrays: Dict[str, Dict[str, Any]]
    fingerprint: Dict[str, Any]
    block_ms: float = 0.0
    label: str = ""
    stats: Dict[str, Any] = field(default_factory=dict)


def _spec_of(leaf) -> List[Any]:
    """The per-dimension mesh-axis spec (the PR-14 sharding-spec
    vocabulary: axis names like ``parts``/``model``, None =
    replicated along that dim), recorded in every shard header."""
    ndim = int(getattr(leaf, "ndim", 0))
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    out: List[Any] = []
    for i in range(ndim):
        e = spec[i] if spec is not None and i < len(spec) else None
        out.append(list(e) if isinstance(e, tuple) else
                   (str(e) if e is not None else None))
    return out


def _owner_procs(leaf) -> List[int]:
    """Process indices owning at least one canonical
    (``replica_id == 0``) shard of ``leaf`` — identical on every
    process (derived from the GLOBAL sharding), which is what lets
    the commit protocol decide barrier-or-not without communicating.
    Host arrays / fully replicated arrays are owned by process 0."""
    if getattr(leaf, "is_fully_replicated", True):
        return [0]
    try:
        procs = sorted({s.device.process_index
                        for s in leaf.global_shards
                        if s.replica_id == 0})
        return procs or [0]
    except Exception:  # noqa: BLE001 - no global view: local owner
        return [int(jax.process_index())]


def _owns_pieces(leaf, proc: int) -> bool:
    """Whether THIS process owns any canonical piece of ``leaf`` —
    the gate in front of every device→host byte: a non-owner must
    never pay D2H traffic for arrays it will not write (the v2
    early-return contract, kept at per-leaf granularity)."""
    if getattr(leaf, "is_fully_replicated", True):
        return proc == 0
    return any(s.replica_id == 0 for s in leaf.addressable_shards)


def _leaf_pieces(key: str, leaf, proc: int) -> List[_Piece]:
    """THIS process's canonical pieces of ``leaf``."""
    if getattr(leaf, "is_fully_replicated", True):
        if proc != 0:
            return []
        return [_Piece(member=key, key=key, index=None,
                       data=np.asarray(leaf))]
    out: List[_Piece] = []
    shape = tuple(int(d) for d in leaf.shape)
    n = 0
    for s in leaf.addressable_shards:
        if s.replica_id != 0:
            continue
        index = [[int(sl.start or 0),
                  int(sl.stop) if sl.stop is not None else dim]
                 for sl, dim in zip(s.index, shape)]
        out.append(_Piece(member=f"{key}@{n}", key=key, index=index,
                          data=np.asarray(s.data)))
        n += 1
    return out


def snapshot_state(params: Any, opt_state: Any, epoch: int,
                   key: Optional[jax.Array] = None,
                   fingerprint: Optional[Dict[str, Any]] = None
                   ) -> Snapshot:
    """Host snapshot of the full training state: the ONLY part of a
    v3 save that must run on the step path (device → host reads; the
    arrays may be donated into the very next step).  D2H copies are
    issued asynchronously for every leaf first, then gathered — the
    per-leaf transfers overlap each other."""
    t0 = time.perf_counter()
    proc = int(jax.process_index())
    flat: List[Tuple[str, Any]] = []
    for prefix, tree in (("params", params), ("opt", opt_state)):
        for kpath, leaf in jax.tree_util.tree_leaves_with_path(tree):
            flat.append((prefix + jax.tree_util.keystr(kpath), leaf))
    for _, leaf in flat:
        if hasattr(leaf, "copy_to_host_async") and \
                _owns_pieces(leaf, proc):
            # best-effort overlap of the D2H issue across leaves —
            # OWNED leaves only (a non-owner process fetching bytes
            # it will never write would put full-tree D2H traffic on
            # every peer's step path); the np.asarray below is the
            # authoritative (blocking) fetch
            try:
                leaf.copy_to_host_async()
            except Exception:  # noqa: BLE001  # roc-lint: ok=swallowed-exception (an unsupported async copy just degrades to the sync fetch below)
                pass
    pieces: List[_Piece] = []
    arrays: Dict[str, Dict[str, Any]] = {}
    owners: set = set()
    for k, leaf in flat:
        arrays[k] = {"shape": [int(d) for d in leaf.shape],
                     "dtype": str(leaf.dtype),
                     "spec": _spec_of(leaf)}
        owners.update(_owner_procs(leaf))
        pieces.extend(_leaf_pieces(k, leaf, proc))
    # loop counters ride as ordinary process-0 members
    scalars: List[Tuple[str, np.ndarray]] = [
        ("__epoch__", np.asarray(epoch, dtype=np.int64))]
    if key is not None:
        scalars.append(("__key__", np.asarray(jax.device_get(key))))
    for k, arr in scalars:
        arrays[k] = {"shape": [int(d) for d in arr.shape],
                     "dtype": str(arr.dtype),
                     "spec": [None] * arr.ndim}
        if proc == 0:
            pieces.append(_Piece(member=k, key=k, index=None, data=arr))
    owners.add(0)
    return Snapshot(epoch=int(epoch), proc=proc,
                    writer_procs=sorted(owners), pieces=pieces,
                    arrays=arrays, fingerprint=fingerprint or {},
                    block_ms=(time.perf_counter() - t0) * 1e3)


def snapshot_trainer(trainer) -> Snapshot:
    """Trainer state → :class:`Snapshot` (the async saver's submit
    payload).  The finite guard is the CALLER's job (checkpoint_
    trainer / CheckpointRotation.save run it right before this)."""
    return snapshot_state(trainer.params, trainer.opt_state,
                          trainer.epoch, getattr(trainer, "key", None),
                          fingerprint=trainer_fingerprint(trainer))


# ------------------------------------------- v3: write + 2-phase commit

def _write_shard(d: str, snap: Snapshot) -> Tuple[str, bytes]:
    """Serialize THIS process's pieces and land them as
    ``shard_<proc>.npz`` via tmp → fsync → rename.  Returns the shard
    file name and its exact bytes (the manifest CRCs the same bytes —
    no re-read, no TOCTOU)."""
    from ..resilience import inject
    name = shard_file_name(snap.proc)
    data = {p.member: p.data for p in snap.pieces}
    header = {
        "version": CHECKPOINT_VERSION,
        "process": snap.proc,
        "epoch": snap.epoch,
        "crc32": {m: _crc(a) for m, a in data.items()},
        "arrays": snap.arrays,
        "pieces": {p.member: {"key": p.key, "index": p.index}
                   for p in snap.pieces},
    }
    data[_HEADER_KEY] = np.frombuffer(
        json.dumps(header).encode("utf-8"), dtype=np.uint8)
    buf = io.BytesIO()
    np.savez(buf, **data)
    raw = buf.getvalue()
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(raw)
            f.flush()
            os.fsync(f.fileno())
        # fault drill site: a SIGKILL here leaves only the .npz.tmp —
        # which restore structurally never picks up (atomicity drill)
        inject.maybe_kill_in_save(snap.epoch)
        os.replace(tmp, os.path.join(d, name))
        _fsync_dir(d)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return name, raw


def commit_manifest(d: str, snap: Snapshot,
                    shards: List[Dict[str, Any]]) -> None:
    """Phase two: publish ``MANIFEST.json`` atomically (tmp → fsync →
    rename → directory fsync).  The manifest IS the commit record —
    until it lands, the checkpoint does not exist to any reader."""
    doc = {"version": CHECKPOINT_VERSION,
           "epoch": snap.epoch,
           "fingerprint": snap.fingerprint,
           "shards": shards}
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".json.tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(d, MANIFEST_NAME))
        _fsync_dir(d)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def write_snapshot(path: str, snap: Snapshot) -> Dict[str, Any]:
    """The full v3 save (CRC + shard write + commit) for an already-
    taken snapshot — jax-free unless more than one process owns
    shards (then the commit barrier), so the async saver can run it
    on its background thread.  Crash-consistent at every byte: shards
    land via atomic rename, the manifest publishes last, and an
    uncommitted (or half-rewritten) directory is invisible to
    ``restore_latest``."""
    from ..resilience import inject
    t0 = time.perf_counter()
    d = os.path.abspath(path)
    os.makedirs(d, exist_ok=True)
    man = os.path.join(d, MANIFEST_NAME)
    if snap.proc == 0 and os.path.exists(man):
        # re-saving a replayed epoch: UN-commit first so a crash mid-
        # rewrite leaves an invisible directory, never a manifest
        # pointing at half-replaced shards
        os.remove(man)
        _fsync_dir(d)
    if len(snap.writer_procs) > 1:
        # un-commit barrier: no writer may rename its shard into
        # place while a previous manifest could still reference the
        # old bytes — without this, a peer's early os.replace races
        # proc 0's un-commit and a crash in that window leaves a live
        # manifest over a half-replaced shard set (found by the
        # level-eight model checker's ckpt-commit model; CRC
        # validation at restore would detect it, but the ordering
        # guarantee is what makes a present manifest ALWAYS valid)
        from ..parallel.multihost import checkpoint_commit_barrier
        checkpoint_commit_barrier(
            f"{os.path.basename(d)}:{snap.epoch}:uncommit")
    my_name = my_raw = None
    if snap.pieces:
        my_name, my_raw = _write_shard(d, snap)
    t_write = time.perf_counter()
    # fault drill site: the exact two-phase-commit window — shards
    # renamed into place, manifest not yet published
    inject.maybe_kill_in_commit(snap.epoch)
    if len(snap.writer_procs) > 1:
        from ..parallel.multihost import checkpoint_commit_barrier
        checkpoint_commit_barrier(f"{os.path.basename(d)}:{snap.epoch}")
    if snap.proc == 0:
        shards = []
        for p in snap.writer_procs:
            name = shard_file_name(p)
            if name == my_name:
                raw = my_raw
            else:
                # a peer's shard, already landed (barrier above) on
                # the shared checkpoint storage
                with open(os.path.join(d, name), "rb") as f:
                    raw = f.read()
            shards.append({"file": name, "process": int(p),
                           "bytes": len(raw),
                           "crc32": zlib.crc32(raw) & 0xFFFFFFFF})
        commit_manifest(d, snap, shards)
    t_commit = time.perf_counter()
    stats = {"epoch": snap.epoch, "path": d,
             "block_ms": round(snap.block_ms, 3),
             "write_ms": round((t_write - t0) * 1e3, 3),
             "commit_ms": round((t_commit - t_write) * 1e3, 3),
             "save_ms": round((t_commit - t0) * 1e3 + snap.block_ms, 3),
             "bytes": len(my_raw) if my_raw is not None else 0,
             "shards": len(snap.writer_procs)}
    snap.stats = stats
    return stats


def save_checkpoint(path: str, params: Any, opt_state: AdamState,
                    epoch: int, key: Optional[jax.Array] = None,
                    fingerprint: Optional[Dict[str, Any]] = None
                    ) -> None:
    """Synchronous v3 save: snapshot + CRC + shard write + manifest
    commit, all on the calling thread.  Every process calls this
    under multi-process SPMD; each writes only the shards it owns and
    process 0 publishes the commit record."""
    snap = snapshot_state(params, opt_state, epoch, key=key,
                          fingerprint=fingerprint)
    write_snapshot(path, snap)


# ------------------------------------------------------------ loaders

def _read_checkpoint(path: str) -> Dict[str, np.ndarray]:
    try:
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    except FileNotFoundError:
        raise
    except Exception as e:
        # torn write, zip-CRC failure, truncation: all one corruption
        # class for the rotation's fallback
        raise CheckpointCorrupt(
            f"{path}: unreadable ({type(e).__name__}: {e})") from e


def _parse_header(data: Dict[str, np.ndarray],
                  path: str) -> Optional[Dict[str, Any]]:
    raw = data.pop(_HEADER_KEY, None)
    if raw is None:
        return None
    try:
        return json.loads(bytes(
            np.asarray(raw, dtype=np.uint8)).decode("utf-8"))
    except Exception as e:
        raise CheckpointCorrupt(
            f"{path}: integrity header unparseable "
            f"({type(e).__name__}: {e})") from e


def _validate_integrity(data: Dict[str, np.ndarray],
                        header: Dict[str, Any], path: str) -> None:
    crcs = header.get("crc32") or {}
    missing = sorted(set(crcs) - set(data))
    extra = sorted(set(data) - set(crcs))
    if missing or extra:
        raise CheckpointCorrupt(
            f"{path}: array set mismatch (missing={missing}, "
            f"unexpected={extra})")
    for name, want in crcs.items():
        got = _crc(data[name])
        if got != int(want):
            raise CheckpointCorrupt(
                f"{path}: CRC32 mismatch at {name!r} "
                f"({got:#010x} != {int(want):#010x})")


def _validate_fingerprint(header: Dict[str, Any],
                          expect: Optional[Dict[str, Any]],
                          path: str) -> None:
    saved = header.get("fingerprint") or {}
    stored_impl = (saved.get("elastic") or {}).get("aggr_impl")
    if stored_impl is not None:
        # a ValueError, not CheckpointCorrupt: the rotation's fallback
        # would only find the same name in the older checkpoints
        check_stored_aggr_impl(stored_impl, path)
    if not expect or not saved:
        return
    ss, es = saved.get("strict") or {}, expect.get("strict") or {}
    bad = sorted(k for k in set(ss) & set(es) if ss[k] != es[k])
    if bad:
        raise CheckpointCorrupt(
            f"{path}: config fingerprint mismatch at {bad} — this "
            f"checkpoint belongs to a different model/dataset/dtype "
            f"(saved {({k: ss[k] for k in bad})}, "
            f"restoring {({k: es[k] for k in bad})})")
    sv, ev = saved.get("elastic") or {}, expect.get("elastic") or {}
    if sv and ev and sv != ev:
        emit("resilience",
             f"elastic restore: checkpoint partition "
             f"P={sv.get('num_parts')} "
             f"({sv.get('part_nodes')}x{sv.get('part_edges')}) -> "
             f"current P={ev.get('num_parts')} "
             f"({ev.get('part_nodes')}x{ev.get('part_edges')}); "
             f"restored arrays are gathered to full host layout, the "
             f"partition is rebuilt from the current plan",
             kind="elastic_restore", saved=sv, current=ev)


def read_manifest(path: str) -> Dict[str, Any]:
    """The committed manifest of a v3 checkpoint directory, or
    :class:`CheckpointCorrupt` — an uncommitted directory IS the
    corruption class (it must be invisible to the fallback scan)."""
    man = os.path.join(path, MANIFEST_NAME)
    try:
        with open(man) as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise CheckpointCorrupt(
            f"{path}: no committed manifest (save died before the "
            f"commit, or not a checkpoint directory)") from None
    except Exception as e:
        raise CheckpointCorrupt(
            f"{man}: manifest unreadable "
            f"({type(e).__name__}: {e})") from e
    if not isinstance(doc, dict) or \
            doc.get("version") != CHECKPOINT_VERSION or \
            not isinstance(doc.get("shards"), list) or not doc["shards"]:
        raise CheckpointCorrupt(f"{man}: malformed manifest")
    return doc


def is_committed(path: str) -> bool:
    """Cheap commit test for rotation scans (existence only; full
    validation happens on the restore attempt, which never touches
    the trainer before it passes)."""
    return os.path.isdir(path) and \
        os.path.exists(os.path.join(path, MANIFEST_NAME))


def _load_v3(path: str) -> Tuple[Dict[str, np.ndarray],
                                 Dict[str, Any]]:
    """Validate + gather a v3 checkpoint directory back to full host
    arrays.  EVERY manifest-listed shard is checked — existence, byte
    count, whole-file CRC32, per-member CRC32 against the shard
    header, and full piece coverage of every array — BEFORE any data
    is returned, so a manifest whose shard went missing can never be
    selected by the fallback scan."""
    doc = read_manifest(path)
    pieces: Dict[str, List[Tuple[Optional[List[List[int]]],
                                 np.ndarray]]] = {}
    metas: Dict[str, Dict[str, Any]] = {}
    for sh in doc["shards"]:
        fp = os.path.join(path, str(sh.get("file")))
        try:
            with open(fp, "rb") as f:
                raw = f.read()
        except OSError as e:
            raise CheckpointCorrupt(
                f"{path}: manifest lists {sh.get('file')} but the "
                f"shard is missing/unreadable ({e})") from e
        if len(raw) != int(sh.get("bytes", -1)) or \
                (zlib.crc32(raw) & 0xFFFFFFFF) != int(sh.get("crc32",
                                                             -1)):
            raise CheckpointCorrupt(
                f"{fp}: shard bytes/CRC32 do not match the committed "
                f"manifest")
        try:
            with np.load(io.BytesIO(raw)) as z:
                data = {k: z[k] for k in z.files}
        except Exception as e:
            raise CheckpointCorrupt(
                f"{fp}: unreadable ({type(e).__name__}: {e})") from e
        header = _parse_header(data, fp)
        if header is None:
            raise CheckpointCorrupt(f"{fp}: shard has no header")
        _validate_integrity(data, header, fp)
        metas.update(header.get("arrays") or {})
        for member, pm in (header.get("pieces") or {}).items():
            pieces.setdefault(pm["key"], []).append(
                (pm.get("index"), data[member]))
    out: Dict[str, np.ndarray] = {}
    for key, meta in metas.items():
        ps = pieces.get(key, [])
        shape = tuple(int(d) for d in meta["shape"])
        total = int(np.prod(shape)) if shape else 1
        if len(ps) == 1 and ps[0][0] is None:
            out[key] = ps[0][1]
            continue
        full = np.zeros(shape, dtype=np.dtype(meta["dtype"]))
        covered = 0
        for index, arr in ps:
            if index is None:
                full[...] = arr
                covered += int(arr.size)
                continue
            full[tuple(slice(lo, hi) for lo, hi in index)] = arr
            covered += int(arr.size)
        if covered != total:
            # gather-on-restore coverage proof: pieces are disjoint
            # by construction (replica_id-0 dedup), so count equality
            # == every element restored exactly once
            raise CheckpointCorrupt(
                f"{path}: array {key!r} gathered {covered}/{total} "
                f"elements from the saved shards (incomplete "
                f"sharded save)")
        out[key] = full
    return out, doc


def _load_legacy_file(path: str) -> Tuple[Dict[str, np.ndarray],
                                          Dict[str, Any]]:
    """v1/v2 single-file loader, each with its loud migration
    warning."""
    data = _read_checkpoint(path)
    header = _parse_header(data, path)
    if header is None:
        emit("resilience",
             f"{os.path.basename(path)}: v1 checkpoint (no integrity "
             f"header) — loading WITHOUT CRC/fingerprint validation",
             kind="v1_checkpoint", path=path)
        return data, {}
    emit("resilience",
         f"{os.path.basename(path)}: legacy v2 single-file "
         f"checkpoint — loading (validated); the next save writes "
         f"the sharded v3 directory format",
         kind="legacy_checkpoint", path=path, version=2)
    _validate_integrity(data, header, path)
    return data, header


def load_checkpoint(path: str, params_template: Any,
                    opt_template: AdamState,
                    expect_fingerprint: Optional[Dict[str, Any]] = None
                    ) -> Tuple[Any, AdamState, int, Optional[jax.Array]]:
    """Restore against templates (e.g. a fresh ``model.init_params`` +
    ``adam_init``); shapes are validated leaf by leaf, every byte
    against the stored CRC32 tables (v3: manifest file CRCs + shard
    member CRCs + coverage; v2: the header table), and the strict
    fingerprint half against ``expect_fingerprint`` — all failures
    raise :class:`CheckpointCorrupt` before anything is returned.
    v1/v2 single-file checkpoints load with a loud warning."""
    if os.path.isdir(path):
        data, doc = _load_v3(path)
        header: Dict[str, Any] = doc
    else:
        data, header = _load_legacy_file(path)
    _validate_fingerprint(header, expect_fingerprint, path)
    params = _unflatten(params_template, data, "params", path)
    opt_state = _unflatten(opt_template, data, "opt", path)
    epoch = int(data["__epoch__"])
    key = jnp.asarray(data["__key__"]) if "__key__" in data else None
    return params, opt_state, epoch, key


def restore_params_only(path: str
                        ) -> Tuple[Dict[str, Any], Dict[str, Any], int]:
    """``(params, fingerprint, epoch)`` from a checkpoint WITHOUT
    constructing a trainer: params come back as the flat name → array
    dict every model's ``init_params`` produces, integrity-validated
    (v3: full manifest + shard validation; v2: the CRC table;
    optimizer state is read past, never materialized on device).  The
    serve export CLI and a cold server process read weights through
    this — paying trainer/dataset setup just to load a checkpoint
    would put minutes of graph-table builds on a path that needs none
    of them.  ``fingerprint`` is the saved fingerprint dict (empty
    for v1 checkpoints) — callers hold its strict half against the
    model they are about to serve."""
    import re
    if os.path.isdir(path):
        data, header = _load_v3(path)
    else:
        data, header = _load_legacy_file(path)
    params: Dict[str, Any] = {}
    # one single-quoted bracket segment ONLY: a nested tree flattens
    # to params['a']['b'], which a greedy (.+) would silently mangle
    # into one corrupt name — such keys must hit the loud error below
    key_re = re.compile(r"^params\['([^']+)'\]$")
    bad = []
    for k, v in data.items():
        if not k.startswith("params"):
            continue
        m = key_re.match(k)
        if m:
            params[m.group(1)] = jnp.asarray(v)
        else:
            bad.append(k)
    if bad or not params:
        raise CheckpointCorrupt(
            f"{path}: expected flat params['<name>'] arrays — not a "
            f"trainer checkpoint, or a non-flat param tree this "
            f"loader does not speak"
            + (f" (unparsed keys: {bad[:3]})" if bad else ""))
    epoch = int(data["__epoch__"]) if "__epoch__" in data else 0
    fingerprint = (header or {}).get("fingerprint") or {}
    return params, fingerprint, epoch


def restore_trainer(trainer, path: str) -> None:
    """Resume a Trainer/DistributedTrainer in place.  The v3 loader
    gathers whatever (P, mesh) layout was saved back to full host
    arrays; distributed trainers then re-replicate across their mesh
    (multihost-safe: ``put_replicated`` assembles from addressable
    shards) — the partition itself was already rebuilt by the
    trainer's own constructor, so a checkpoint from a different P
    restores cleanly (elastic restart)."""
    params, opt_state, epoch, key = load_checkpoint(
        path, trainer.params, trainer.opt_state,
        expect_fingerprint=trainer_fingerprint(trainer))
    mesh = getattr(trainer, "mesh", None)
    if mesh is not None:
        from ..parallel.distributed import put_replicated
        params, opt_state = put_replicated((params, opt_state), mesh)
    trainer.params = params
    trainer.opt_state = opt_state
    trainer.epoch = epoch
    if key is not None:
        trainer.key = key


def checkpoint_trainer(trainer, path: str) -> None:
    """Save a trainer's state synchronously (format v3).  EVERY
    trainer save passes the finite guard first (params + opt state in
    one jitted reduction, one device sync — resilience/recovery.
    check_params_finite): a poisoned state must never persist,
    whether the save came from the recovery rotation, the CLI's
    --checkpoint paths, or an emergency preemption save.  Under
    multi-process SPMD every process participates — each writes only
    the shard file it owns (``shard_<proc>.npz``, the per-process
    filename the artifact-lock lint demands) and process 0
    (``jax.process_index() == 0``) publishes the commit manifest;
    with today's fully replicated state that degenerates to process 0
    writing everything, the v2 single-writer handshake."""
    from ..resilience.recovery import check_params_finite
    check_params_finite(trainer.params, trainer.opt_state)
    snap = snapshot_trainer(trainer)
    if jax.process_count() > 1 and jax.process_index() != 0 and \
            not snap.pieces:
        # nothing owned here and no barrier expected: the replicated
        # degenerate case keeps the v2 early return
        if len(snap.writer_procs) <= 1:
            return
    write_snapshot(path, snap)

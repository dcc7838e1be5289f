"""Multi-host (DCN x ICI) distributed runtime.

The reference runs multi-machine through Legion address spaces over
GASNet (``Makefile:26``) with NCCL linked for collectives
(``nccl_task.cu:19-38``; the multi-rank init is dead-coded,
``gnn.cc:630-642``) and a mapper that round-robins partitions across
machines first (``gnn_mapper.cc:120-131``).  The TPU-native
equivalents here:

- :func:`init_distributed` — ``jax.distributed.initialize`` wrapper
  (the NCCL-communicator/GASNet bootstrap analog); env-driven so the
  same entry point works under any launcher.
- :func:`make_parts_mesh` — a 1-D ``'parts'`` mesh laid out so that
  consecutive partitions land on the same host: the ring/all-gather
  halo then crosses DCN only ``num_hosts`` times per rotation instead
  of every hop (the mapper's machine-first round-robin solved the
  inverse problem — here locality, not spread, minimizes the slow
  link).
- :func:`process_local_parts` / :func:`make_sharded_array` — each host
  materializes only its own partitions' rows and the global jax.Array
  is assembled from per-process local shards
  (``jax.make_array_from_single_device_arrays``) — the analog of the
  reference's per-partition loader tasks running on each node's CPUs
  (``load_task.cu:201-269``) rather than one host broadcasting.

Single-process (including the 8-virtual-device CPU test rig) is the
degenerate case throughout; nothing here requires real multi-host
hardware to compile or test.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import PARTS_AXIS


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids: Optional[Sequence[int]] = None
                     ) -> None:
    """Initialize the JAX distributed runtime (multi-host DCN).

    Arguments default from the standard env vars
    (``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``,
    ``JAX_PROCESS_ID``), so launchers only need to export those.  A
    no-op when single-process (no coordinator configured) — the
    single-host paths then work unchanged.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if coordinator_address is None:
        return
    platforms = str(jax.config.jax_platforms
                    or os.environ.get("JAX_PLATFORMS") or "")
    if "cpu" in platforms or not platforms:
        # XLA:CPU's default in-process collectives cannot cross
        # address spaces ("Multiprocess computations aren't
        # implemented on the CPU backend") — multi-process CPU runs
        # (the 2-process DCN parity tests, loopback rehearsals of pod
        # topologies) need the Gloo transport selected BEFORE the
        # backend initializes.  Armed too when the platform is
        # auto-detected (empty): the flag only shapes the CPU client,
        # which accelerator-backend collectives never route through,
        # so TPU/GPU pods are unaffected; an EXPLICIT non-cpu platform
        # list skips it.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    num_processes = num_processes if num_processes is not None else int(
        os.environ.get("JAX_NUM_PROCESSES", "1"))
    process_id = process_id if process_id is not None else int(
        os.environ.get("JAX_PROCESS_ID", "0"))
    # pin the event-clock identity the moment the process id is known:
    # trainer-setup events (partition stats, plan echoes) fire BEFORE
    # the run manifest's own set_clock_identity, and a launcher that
    # passes process_id programmatically (this function's argv path)
    # never exported JAX_PROCESS_ID — without this, every process's
    # early events would stamp proc=0 and mis-lane in the merged
    # timeline
    from ..obs.events import set_clock_identity
    set_clock_identity(proc=process_id)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)


def checkpoint_commit_barrier(tag: str) -> None:
    """The checkpoint-v3 two-phase-commit rendezvous: every process
    has renamed its shard files into place; after this barrier,
    process 0 publishes MANIFEST.json (utils/checkpoint.
    write_snapshot).  Only reached when MORE than one process owns
    shards — today's fully replicated state (process 0 owns
    everything) never needs it, so the degenerate path stays
    barrier-free exactly like the v2 single-writer handshake.
    Single-process is a no-op.  A dead peer wedges the survivors
    here; the heartbeat dates the stall and — with
    ``ROC_TPU_STALL_TIMEOUT_S`` armed — promotes it into a
    StallFailure the recovery loop can checkpoint-restart
    (obs/heartbeat.py), the same contract as the setup collectives
    above."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    from ..obs.heartbeat import Heartbeat
    with Heartbeat("ckpt_commit_barrier", op=tag):
        multihost_utils.sync_global_devices(f"roc_tpu:ckpt:{tag}")


def make_parts_mesh(num_parts: Optional[int] = None,
                    devices: Optional[List] = None,
                    model: int = 1) -> Mesh:
    """``'parts'`` (or 2-D ``('parts', 'model')`` when ``model > 1``)
    mesh across all processes' devices — alias of
    :func:`roc_tpu.parallel.distributed.make_mesh` (one constructor,
    one partition->device layout; see its docstring for the DCN
    locality invariant).  The model axis is the FAST axis of the
    device order, so a partition's model group stays within one
    host's ICI domain whenever the host owns ``model`` consecutive
    devices."""
    from .distributed import make_mesh
    return make_mesh(num_parts, devices, model=model)


def _part_device_rows(mesh: Mesh) -> np.ndarray:
    """Mesh devices as a ``[parts, model]`` grid (model = 1 for the
    1-D mesh) — row ``p`` holds every device that carries partition
    ``p``'s ``P('parts')`` shard (replicated over the model axis)."""
    return mesh.devices.reshape(mesh.devices.shape[0], -1)


def process_local_parts(mesh: Mesh) -> List[int]:
    """Partition indices with at least one device on this process —
    the set of shards this host must load (the reference's per-node
    loader tasks, ``load_task.cu:201-269``, selected by the mapper;
    here selected by mesh placement).  On a 2-D mesh a partition is
    local when ANY of its model-axis devices is."""
    pid = jax.process_index()
    return [i for i, row in enumerate(_part_device_rows(mesh))
            if any(d.process_index == pid for d in row)]


def make_sharded_array(mesh: Mesh, local_parts: List[int],
                       local_shards: Sequence[np.ndarray],
                       global_shape: Tuple[int, ...]) -> jax.Array:
    """Assemble a ``P('parts')``-sharded global array from this
    process's shard data only (no cross-host broadcast).

    local_shards[i] is the [1, ...] slice for partition
    ``local_parts[i]``.  On a single process this reduces to a plain
    ``device_put`` of the stacked array.  On a 2-D mesh each
    partition's shard is replicated onto every addressable device of
    its model row — the data axes never shard over ``model``.
    """
    sharding = NamedSharding(mesh, P(PARTS_AXIS))
    rows = _part_device_rows(mesh)
    pid = jax.process_index()
    singles = []
    for part, shard in zip(local_parts, local_shards):
        arr = np.ascontiguousarray(shard)
        for d in rows[part]:
            if d.process_index == pid:
                singles.append(jax.device_put(arr, d))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, singles)


def _allreduce_part_vec_max(mesh: Mesh, local: List[int],
                            vecs: dict) -> np.ndarray:
    """Elementwise max over per-partition int vectors across all hosts
    (each host knows only its own parts' vectors) — O(P * len) tiny
    collective.  Single-process short-circuits."""
    if jax.process_count() == 1:
        return np.max(np.stack([vecs[p] for p in local]), axis=0)
    import jax.numpy as jnp
    num_parts = int(mesh.devices.shape[0])
    width = len(next(iter(vecs.values())))
    arr = make_sharded_array(
        mesh, local,
        [np.asarray(vecs[p], dtype=np.int64)[None] for p in local],
        (num_parts, width))
    # one-shot bootstrap collective at table build, not a training
    # step — compile telemetry would be noise: roc-lint: ok=bare-jit
    reduce = jax.jit(lambda a: jnp.max(a, axis=0),
                     out_shardings=NamedSharding(mesh, P()))
    # a peer process that died before this DCN rendezvous hangs every
    # survivor here forever; the watchdog dates the stall and — with
    # ROC_TPU_STALL_TIMEOUT_S armed — converts it into a StallFailure
    # the recovery loop can checkpoint-restart (obs/heartbeat.py)
    from ..obs.heartbeat import Heartbeat
    with Heartbeat("multihost_collective", op="part_vec_max"):
        return np.asarray(reduce(arr))


def shard_dataset_local(dataset, pg, mesh: Mesh, dtype=None,
                        aggr_impl: str = "segment",
                        halo: str = "gather",
                        section_rows: Optional[int] = None,
                        sect_sub_w: int = 8, sect_u16: bool = False,
                        bdense_min_fill: int = 64,
                        bdense_a_budget: Optional[int] = 2 << 30,
                        bdense_group: int = 1):
    """Multi-host version of ``distributed.shard_dataset``: each process
    BUILDS and uploads only its own partitions' shards — row-sliced
    loads via :class:`roc_tpu.core.source.DataSource`, per-partition
    column fills, the tables through ``models/tables.py build_tables``
    over this process's parts.  No whole-graph O(E) materialization
    per host beyond the O(V) row-pointer metadata (the reference's
    per-partition loader tasks, ``load_task.cu:41-51,201-245``).
    Returns the same ``ShardedData`` so ``DistributedTrainer`` works
    unchanged.

    ``dataset`` may be a Dataset (in-memory; slices are views) or any
    DataSource (e.g. ``FileSource`` for the on-disk reference layout).
    ``pg`` may be a PartitionPlan — column data is only read for local
    parts.  Every shape the SPMD program needs identical on every host
    (the ELL bucket plan, the sectioned chunk plan and windows, the
    ring's pair width, the bdense block count and packing) is agreed
    by an O(P) collective over per-part stats (never a whole-graph
    pass).  The tables carry no baked fused-normalization weights: a
    fused model scales in the op.
    """
    import jax.numpy as jnp
    from ..core.graph import MASK_NONE
    from ..core.partition import partition_col
    from ..core.source import as_source
    from ..models.tables import build_tables
    from ..obs.events import span
    from .distributed import ShardedData, parts_view

    if dtype is None:
        dtype = jnp.float32
    src = as_source(dataset)
    local = process_local_parts(mesh)
    P, pn = pg.num_parts, pg.part_nodes

    def put(per, what="tables"):
        """A P('parts')-sharded array of this process's per-part
        arrays."""
        first = np.asarray(per[local[0]])
        with span("setup.upload", what=what) as s:
            out = make_sharded_array(
                mesh, local,
                [np.ascontiguousarray(np.asarray(per[p])[None])
                 for p in local], (P,) + first.shape)
            s["h2d_bytes"] += first.nbytes * len(local)
        return out

    def node_field(get, fill, np_dtype, extra=()):
        per = {}
        for p in local:
            l, r = pg.bounds[p]
            per[p] = np.full((pn,) + extra, fill, dtype=np_dtype)
            if r >= l:
                per[p][:r - l + 1] = get(l, r + 1)
        return per

    view = parts_view(
        pg, local, lambda p: partition_col(pg, src.col_slice, p), put,
        lambda per: _allreduce_part_vec_max(mesh, local, per),
        remap=halo != "ring")
    notes: dict = {}
    tables = build_tables(
        "ring" if halo == "ring" else aggr_impl, view, notes=notes, sect_sub_w=sect_sub_w, sect_u16=sect_u16,
        section_rows=section_rows, bdense_min_fill=bdense_min_fill,
        bdense_a_budget=bdense_a_budget, bdense_group=bdense_group)
    with span("setup.tables", table="padded_rows"):
        feats = {p: a.astype(np.dtype(dtype), copy=False)
                 for p, a in node_field(src.features, 0, np.float32,
                                        (src.in_dim,)).items()}
        labels = node_field(src.labels, 0, np.int32)
        mask = node_field(src.mask, MASK_NONE, np.int32)
    return ShardedData(
        feats=put(feats, "features"), labels=put(labels, "labels"),
        mask=put(mask, "mask"), tables=tables,
        in_degree=put({p: pg.part_in_degree[p] for p in local}),
        bd_occupancy=notes.get("bd_occupancy", ()),
        ring_padding_ratio=notes.get("ring_padding_ratio"))

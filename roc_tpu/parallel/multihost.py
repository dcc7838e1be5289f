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


def _allreduce_part_stats(mesh: Mesh, local: List[int],
                          stats: dict) -> Tuple[int, int]:
    """(global max of stat[0], global sum of stat[1]) over all
    partitions, where each host knows only its own parts' values.
    Single-process short-circuits; multi-host runs one tiny [P, 2]
    collective — the O(P) agreement that replaces a whole-graph pass.
    """
    if jax.process_count() == 1:
        return (max(v[0] for v in stats.values()),
                sum(v[1] for v in stats.values()))
    import jax.numpy as jnp
    num_parts = int(mesh.devices.shape[0])
    arr = make_sharded_array(
        mesh, local,
        [np.asarray([[stats[p][0], stats[p][1]]], dtype=np.int64)
         for p in local],
        (num_parts, 2))
    # one-shot bootstrap collective — roc-lint: ok=bare-jit
    reduce = jax.jit(
        lambda a: jnp.stack([jnp.max(a[:, 0]), jnp.sum(a[:, 1])]),
        out_shardings=NamedSharding(mesh, P()))
    # same DCN-rendezvous hazard (and the same deadline promotion) as
    # _allreduce_part_vec_max above
    from ..obs.heartbeat import Heartbeat
    with Heartbeat("multihost_collective", op="part_stats"):
        out = np.asarray(reduce(arr))
    return int(out[0]), int(out[1])


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
    column fills, per-partition ELL tables against a degree-derived
    global shape plan.  No whole-graph O(E) materialization per
    host beyond the O(V) row-pointer metadata (the reference's
    per-partition loader tasks, ``load_task.cu:41-51,201-245``).
    Returns the same ``ShardedData`` so ``DistributedTrainer`` works
    unchanged.

    ``dataset`` may be a Dataset (in-memory; slices are views) or any
    DataSource (e.g. ``FileSource`` for the on-disk reference layout).
    ``pg`` may be a PartitionPlan — column data is only read for local
    parts.  ``halo='ring'`` is partition-local too: per-part pair
    lists from local column reads, with the uniform pair width agreed
    via an O(P) collective (never a whole-graph pass).
    ``aggr_impl='bdense'`` agrees the uniform per-part block count and
    the residual sectioned chunk plan the same O(P) way.
    """
    import jax.numpy as jnp
    from ..core.ell import build_ell, ell_shape_plan, place_ell_part
    from ..core.graph import MASK_NONE
    from ..core.partition import partition_col
    from ..core.source import as_source
    from .distributed import ShardedData, remap_col_to_padded

    if dtype is None:
        dtype = jnp.float32
    src = as_source(dataset)
    local = process_local_parts(mesh)
    P, pn, pe = pg.num_parts, pg.part_nodes, pg.part_edges

    def put_parts(build, shape, np_dtype):
        """Assemble a P('parts')-sharded array from per-part builders
        run ONLY for this process's partitions."""
        shards = [np.ascontiguousarray(
            build(p)[None].astype(np_dtype, copy=False)) for p in local]
        return make_sharded_array(mesh, local, shards, (P,) + shape)

    def node_field(get, fill, np_dtype, extra=()):
        def build(p):
            l, r = pg.bounds[p]
            out = np.full((pn,) + extra, fill, dtype=np_dtype)
            if r >= l:
                out[:r - l + 1] = get(l, r + 1)
            return out
        return build

    if halo == "ring":
        # Fully partition-local ring prep: pair lists from this host's
        # own column reads; the uniform pair width (an SPMD shape, so
        # every host must agree) comes from an O(P) max/sum collective
        # over per-part stats — never a whole-graph pass.
        from .ring import (build_ring_pairs, pack_ring_part,
                           round_pair_edges)
        pairs = {p: build_ring_pairs(
            pg, p, partition_col(pg, src.col_slice, p)) for p in local}
        stats = {p: (max((d.shape[0] for _, d in pairs[p].values()),
                         default=1),
                     sum(d.shape[0] for _, d in pairs[p].values()))
                 for p in local}
        max_pair, total_real = _allreduce_part_stats(mesh, local, stats)
        pair_edges = round_pair_edges(max_pair)
        # pack once per part — each pack allocates two [P, pair_edges]
        # tables (hundreds of MB at Amazon-2M scale)
        packed = {p: pack_ring_part(pairs[p], P, pair_edges, pn)
                  for p in local}
        ring_src = put_parts(lambda p: packed[p][0], (P, pair_edges),
                             np.int32)
        ring_dst = put_parts(lambda p: packed[p][1], (P, pair_edges),
                             np.int32)
        stub = lambda p: np.zeros(1, np.int32)
        return ShardedData(
            feats=put_parts(node_field(src.features, 0, np.float32,
                                       (src.in_dim,)),
                            (pn, src.in_dim), np.dtype(dtype)),
            labels=put_parts(node_field(src.labels, 0, np.int32), (pn,),
                             np.int32),
            mask=put_parts(node_field(src.mask, MASK_NONE, np.int32),
                           (pn,), np.int32),
            edge_src=put_parts(stub, (1,), np.int32),
            edge_dst=put_parts(stub, (1,), np.int32),
            in_degree=put_parts(lambda p: pg.part_in_degree[p], (pn,),
                                np.int32),
            ell_row_pos=put_parts(stub, (1,), np.int32),
            ring_idx=(ring_src, ring_dst),
            ring_padding_ratio=(P * P * pair_edges) / max(total_real, 1),
        )

    # local parts' padded columns, remapped once and reused by both the
    # edge_src field and the ELL table build
    cols = {p: remap_col_to_padded(pg, partition_col(pg, src.col_slice, p))
            for p in local}
    use_stub = aggr_impl != "segment"

    def edge_src_build(p):
        return cols[p]

    def edge_dst_build(p):
        return np.repeat(np.arange(pn, dtype=np.int32),
                         np.diff(pg.part_row_ptr[p]))

    ell_idx = ()
    ell_row_id = ()
    ell_row_pos = put_parts(lambda p: np.zeros(1, np.int32), (1,),
                            np.int32)
    ring_idx = ()
    if aggr_impl == "ell":
        # plan from part_row_ptr — the SAME degrees part_tables' bucket
        # build sees (padding edges can inflate the last real row's
        # degree when real_nodes[p] == part_nodes; see ell_shape_plan)
        widths, rows_per_width = ell_shape_plan(pg.part_row_ptr,
                                                pg.real_nodes)
        dummy = P * pn

        def part_tables(p):
            n = int(pg.real_nodes[p])
            ptr = pg.part_row_ptr[p, :n + 1].astype(np.int64)
            buckets = build_ell(ptr, edge_src_build(p))
            return place_ell_part(buckets, widths, rows_per_width, pn,
                                  dummy)

        tables = {p: part_tables(p) for p in local}
        ell_idx = tuple(
            put_parts(lambda p, wi=wi: tables[p][0][wi],
                      (rows_per_width[w], w), np.int32)
            for wi, w in enumerate(widths))
        ell_row_pos = put_parts(lambda p: tables[p][1], (pn,), np.int32)
        ell_row_id = tuple(
            put_parts(lambda p, wi=wi: tables[p][2][wi],
                      (rows_per_width[w],), np.int32)
            for wi, w in enumerate(widths))

    sect_idx = ()
    sect_sub_dst = ()
    sect_meta = ()
    flat_win = 0
    flat_bands = ()

    def agreed_windows(sects):
        """Per-section ``(win_rows, bands)`` every host compiles with:
        the max over ALL parts of each table's own destination-window
        height and of each tile's band (the parts share the chunk
        plan, hence the tiles).  Both exist only once the tables are
        built, so this is a second O(P * n_sec) exchange after the
        chunk plan's — same collective, same place in every host's
        sequence."""
        first = sects[local[0]]
        agreed = [int(v) for v in _allreduce_part_vec_max(
            mesh, local, {p: np.asarray(
                list(sects[p].win_rows)
                + [b for tb in sects[p].bands for _, b in tb])
                for p in local})]
        rest = iter(agreed[len(first.win_rows):])
        bands = [tuple((t, next(rest)) for t, _ in tb)
                 for tb in first.bands]
        return tuple(zip(agreed[:len(first.win_rows)], bands))

    if aggr_impl in ("attn_flat8", "flat_sum"):
        # the uniform flat layout (attention's attn_flat8 and the sum
        # path's flat_sum share it), partition-local: ONE section
        # spanning all gathered sources (same layout shard_dataset
        # builds; DistributedTrainer routes these to the flat8 gctx
        # fields), chunk plan agreed via the O(P) collective.  No
        # baked fused weights multihost (shard_dataset_local has no
        # fuse path for any impl) — the builder's generic d-scaling
        # fallback covers fused configs when flat8_w is None
        from ..core.ell import (FLAT_SEG_ROWS, clean_part_ptr,
                                section_sub_counts,
                                sectioned_from_graph, sectioned_plan)
        src_rows = P * pn
        ptrs = {p: clean_part_ptr(pg.part_row_ptr[p], pg.real_nodes[p],
                                  pn) for p in local}
        cnts = {p: section_sub_counts(
            ptrs[p], cols[p][:int(ptrs[p][-1])], pn, src_rows,
            src_rows) for p in local}
        counts_max = _allreduce_part_vec_max(mesh, local, cnts)
        plan = sectioned_plan(counts_max, seg_rows=FLAT_SEG_ROWS)
        sects = {p: sectioned_from_graph(
            ptrs[p], cols[p][:int(ptrs[p][-1])], pn, src_rows=src_rows,
            section_rows=src_rows, chunks_plan=plan,
            counts=cnts[p]) for p in local}
        sect_idx = (put_parts(lambda p: sects[p].idx[0],
                              (*plan[0], 8), np.int32),)
        sect_sub_dst = (put_parts(lambda p: sects[p].sub_dst[0],
                                  plan[0], np.int32),)
        if aggr_impl == "flat_sum":
            flat_win, flat_bands = agreed_windows(sects)[0]

    def local_sectioned_tables(ptrs, colmap):
        """Stacked sectioned tables from per-part (ptr, cols) dicts —
        the ONE multihost implementation of the uniform-chunk-plan
        agreement (O(P * n_sec) elementwise-max collective over
        per-part sub-row counts, same pattern as the ring's pair
        width; never a whole-graph pass).  Shared by the 'sectioned'
        branch and the bdense residual, mirroring
        distributed._sectioned_tables."""
        from ..core.ell import (default_section_rows,
                                section_sub_counts, sectioned_from_graph,
                                sectioned_plan)
        sec_rows = (section_rows if section_rows is not None
                    else default_section_rows(sect_u16))
        idx_np_dtype = np.uint16 if sect_u16 else np.int32
        src_rows = P * pn
        cnts = {p: section_sub_counts(
            ptrs[p], colmap[p], pn, src_rows,
            sec_rows, sub_w=sect_sub_w) for p in local}
        counts_max = _allreduce_part_vec_max(mesh, local, cnts)
        plan = sectioned_plan(counts_max)
        sects = {p: sectioned_from_graph(
            ptrs[p], colmap[p], pn, src_rows=src_rows,
            section_rows=sec_rows, chunks_plan=plan,
            counts=cnts[p], sub_w=sect_sub_w) for p in local}
        if sect_u16:
            sects = {p: s.with_idx_dtype(np.uint16)
                     for p, s in sects.items()}
        first = sects[local[0]]
        return (
            tuple(put_parts(lambda p, s=s: sects[p].idx[s],
                            (*plan[s], sect_sub_w), idx_np_dtype)
                  for s in range(len(first.idx))),
            tuple(put_parts(lambda p, s=s: sects[p].sub_dst[s],
                            plan[s], np.int32)
                  for s in range(len(first.sub_dst))),
            tuple((st, sz, *wb) for st, sz, wb in zip(
                first.sec_starts, first.sec_sizes,
                agreed_windows(sects))))

    if aggr_impl == "sectioned":
        from ..core.ell import clean_part_ptr
        ptrs = {p: clean_part_ptr(pg.part_row_ptr[p], pg.real_nodes[p],
                                  pn) for p in local}
        sect_idx, sect_sub_dst, sect_meta = local_sectioned_tables(
            ptrs, {p: cols[p][:int(ptrs[p][-1])] for p in local})

    bd_tabs = ()
    bd_vpad = 0
    bd_src_vpad = 0
    bd_occupancy = ()
    if aggr_impl == "bdense":
        # partition-local block-dense plans over the rectangular tile
        # space (local dst rows x gathered sources), exactly
        # distributed.shard_dataset's layout.  The two SPMD shapes
        # every host must agree on — the uniform per-part block count
        # and the residual sectioned chunk plan — come from the same
        # O(P) collectives the sectioned/ring branches use; no
        # whole-graph pass.
        from ..core.ell import clean_part_ptr
        from ..ops.blockdense import (BLOCK, U4_MAX, pack_a_u4,
                                      plan_blocks)
        src_rows = P * pn
        ptrs = {p: clean_part_ptr(pg.part_row_ptr[p], pg.real_nodes[p],
                                  pn) for p in local}

        def _mk(budget):
            # group>1 plans arrive per-part group-aligned BEFORE the
            # nblk_max collective: every host's count is a group
            # multiple, so the uniform stacked tail below pads in
            # whole dummy-dst groups
            return {p: plan_blocks(
                ptrs[p], cols[p][:int(ptrs[p][-1])], pn,
                min_fill=bdense_min_fill, a_budget_bytes=budget,
                num_cols=src_rows, group=bdense_group) for p in local}

        # the 2x-budget-then-pack policy (plan_blocks_packed), decided
        # GLOBALLY: one more O(P) collective agrees the max slot
        # multiplicity, so every host packs (or not) identically and
        # the SPMD table keeps one trailing width.  Branches below
        # depend only on globally-reduced values — every host runs
        # the SAME collective sequence.
        plans = _mk(bdense_a_budget * 2
                    if bdense_a_budget is not None else None)
        nblk_max, _ = _allreduce_part_stats(
            mesh, local, {p: (plans[p].n_blocks, 0) for p in local})
        max_mult, _ = _allreduce_part_stats(
            mesh, local,
            {p: (int(plans[p].a_blocks.max())
                 if plans[p].n_blocks else 0, 0) for p in local})
        packable = max_mult <= U4_MAX
        if packable:
            # pack_a_u4 packs EMPTY parts too — a zero-block part on
            # one host must still stack at the uniform u4 width
            plans = {p: pack_a_u4(plans[p]) for p in local}
        elif bdense_a_budget is not None and \
                nblk_max * BLOCK * BLOCK > bdense_a_budget:
            # some part over the true cap and packing can't save it:
            # re-plan at 1x and re-agree the uniform block count
            plans = _mk(bdense_a_budget)
            nblk_max, _ = _allreduce_part_stats(
                mesh, local,
                {p: (plans[p].n_blocks, 0) for p in local})
        bd_occupancy = tuple(plans[p].occupancy() for p in local)
        if nblk_max:
            bd_vpad = plans[local[0]].vpad
            bd_src_vpad = plans[local[0]].src_vpad
            n_dst_tiles = bd_vpad // BLOCK
            a_w = BLOCK // 2 if packable else BLOCK

            def bd_field(get, fill, np_dtype, extra=()):
                def build(p):
                    pl = plans[p]
                    out = np.full((nblk_max,) + extra, fill,
                                  dtype=np_dtype)
                    out[:pl.n_blocks] = get(pl)
                    return out
                return build
            # padding blocks: zero A scattered into the dummy output
            # tile — numerically inert, same scheme as shard_dataset
            bd_tabs = (
                put_parts(bd_field(lambda pl: pl.a_blocks, 0, np.uint8,
                                   (BLOCK, a_w)),
                          (nblk_max, BLOCK, a_w), np.uint8),
                put_parts(bd_field(lambda pl: pl.src_blk, 0, np.int32),
                          (nblk_max,), np.int32),
                put_parts(bd_field(lambda pl: pl.dst_blk, n_dst_tiles,
                                   np.int32),
                          (nblk_max,), np.int32))
        # residual scattered edges -> the stacked sectioned tables
        # (every edge, when no tile qualifies anywhere)
        sect_idx, sect_sub_dst, sect_meta = local_sectioned_tables(
            {p: plans[p].res_row_ptr for p in local},
            {p: plans[p].res_col for p in local})

    stub_build = lambda p: np.zeros(1, np.int32)
    return ShardedData(
        feats=put_parts(node_field(src.features, 0, np.float32,
                                   (src.in_dim,)),
                        (pn, src.in_dim), np.dtype(dtype)),
        labels=put_parts(node_field(src.labels, 0, np.int32), (pn,),
                         np.int32),
        mask=put_parts(node_field(src.mask, MASK_NONE, np.int32), (pn,),
                       np.int32),
        edge_src=put_parts(stub_build if use_stub else edge_src_build,
                           (1,) if use_stub else (pe,), np.int32),
        edge_dst=put_parts(stub_build if use_stub else edge_dst_build,
                           (1,) if use_stub else (pe,), np.int32),
        in_degree=put_parts(lambda p: pg.part_in_degree[p], (pn,),
                            np.int32),
        ell_idx=ell_idx,
        ell_row_pos=ell_row_pos,
        ell_row_id=ell_row_id,
        ring_idx=ring_idx,
        sect_idx=sect_idx,
        sect_sub_dst=sect_sub_dst,
        sect_meta=sect_meta,
        flat_win=flat_win,
        flat_bands=flat_bands,
        bd_tabs=bd_tabs,
        bd_vpad=bd_vpad,
        bd_src_vpad=bd_src_vpad,
        bd_occupancy=bd_occupancy,
        bd_group=bdense_group if bd_tabs else 1,
    )

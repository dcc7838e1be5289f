#!/usr/bin/env python
"""Micro-benchmark: neighbor-aggregation implementations at Reddit scale.

The reference's hot loop (``scattergather_kernel.cu:20-76``) is an
O(E * F) irregular CSR sum; this script times our implementations of the
same op on one chip to pick the framework default.

Usage: python benchmarks/micro_agg.py [--nodes N] [--edges E] [--dim F]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench(fn, iters=10):
    """Median wall ms.  Synchronizes by fetching a scalar reduction of
    the output; the fetch's ~constant overhead is printed up front as
    the "sync overhead" line."""
    import jax.numpy as jnp
    out = fn()
    float(jnp.sum(out))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        float(jnp.sum(out))
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=232_965)
    ap.add_argument("--edges", type=int, default=114_848_857)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--dtype", type=str, default="float32")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--impls", type=str,
                    default="ell,pallas,scan:2048,scan:4096,blocked:1024")
    ap.add_argument("--seg-rows", type=int, default=131_072,
                    help="sectioned carry-scan chunk size (sub-rows)")
    from _substrates import GRAPH_SPEC_HELP
    ap.add_argument("--graph", type=str, default="random",
                    help=GRAPH_SPEC_HELP)
    ap.add_argument("--reorder", type=str, default="none",
                    help="none | bfs | lpa — relabel vertices before "
                         "table build (core/reorder.py)")
    ap.add_argument("--a-budget", type=int, default=2 << 30,
                    help="bdense uint8 A-table byte cap (densest "
                         "blocks kept; 0 = uncapped).  The 2 GiB "
                         "default binds at Reddit scale: 6 GiB + "
                         "bdense:32 lifts dense_frac 0.52 -> 0.81")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from _substrates import graph_from_spec, reorder_graph
    from roc_tpu.core.partition import padded_edge_list
    from roc_tpu.ops.aggregate import aggregate, aggregate_ell

    V, E, F = args.nodes, args.edges, args.dim
    dev = jax.devices()[0]
    print(f"# device={dev.platform} {dev.device_kind} V={V} E={E} F={F}")
    # fetch-overhead calibration: trivial computation + same sync path
    z = jnp.zeros((1024, F))
    f0 = jax.jit(lambda x: x + 1.0)
    print(f"# sync overhead ~{bench(lambda: f0(z), args.iters):.1f} ms "
          f"(subtract from rows below)")
    g = graph_from_spec(args.graph, V, E)
    g, reorder_s = reorder_graph(
        g, args.reorder, cache_key=f"{args.graph}_{V}_{E}")
    if reorder_s:
        print(f"# {args.reorder} reorder: {reorder_s:.1f}s")
    # 'mixed' is the TRAINER's dtype flag (fp32 params + bf16 compute);
    # here the aggregation input itself is what's typed, so map it to
    # bf16 instead of dying after a multi-minute reorder pass
    dtype = jnp.bfloat16 if args.dtype == "mixed" else getattr(jnp, args.dtype)
    feats_np = np.random.RandomState(0).rand(V + 1, F).astype(np.float32)
    feats_np[-1] = 0
    feats = jnp.asarray(feats_np, dtype=dtype)
    gb = E * F * feats.dtype.itemsize / 1e9

    ell_cache = {}

    def get_ell():
        if "t" not in ell_cache:
            from roc_tpu.core.ell import ell_from_graph
            t0 = time.time()
            ell = ell_from_graph(g.row_ptr, g.col_idx, V)
            ell_cache["prep"] = time.time() - t0
            ell_cache["table"] = ell
            ell_cache["t"] = (
                tuple(jnp.asarray(i[0]) for i in ell.idx),
                jnp.asarray(ell.row_pos[0]))
        return ell_cache["t"], ell_cache["prep"]

    # the fused-normalization race (chain-IMPL vs fused-IMPL rows):
    # d = deg^-1/2 over the dst-major CSR, the same vector the GCN
    # sandwich applies on both sides
    from roc_tpu.ops.norm import inv_sqrt_degree_np
    d_np = inv_sqrt_degree_np(np.diff(g.row_ptr))
    d_ext = np.concatenate([d_np, np.zeros(1, np.float32)])
    dj = jnp.asarray(d_np, dtype=dtype)
    dj_ext = jnp.asarray(d_ext, dtype=dtype)
    dj32 = jnp.asarray(d_np)  # fp32, for the pallas epilogue kernel

    for spec in args.impls.split(","):
        parts = spec.split(":")
        impl = parts[0]
        chunk = int(parts[1]) if len(parts) > 1 else 1024
        if impl.startswith(("chain-", "fused-")):
            # the fused-normalization race (ISSUE 1): 'chain-X' runs
            # the UNFUSED GCN sandwich relu(d * agg_X(d * x)) as the
            # model's separate ops would; 'fused-X' runs the same
            # chain with the D^-1/2 scales baked into the tables
            # (ell/sectioned weight tables, bdense in-register tile
            # scales, the hand-written kernel trio for pallas).
            # Specs: {chain,fused}-{ell,sectioned,bdense,pallas};
            # bdense takes :MINFILL[:GROUP] like the plain row.
            mode, base = impl.split("-", 1)
            t0 = time.time()
            try:
                if base in ("ell", "pallas"):
                    (idx, pos), _ = get_ell()
                    if base == "pallas":
                        from roc_tpu.kernels.ell_spmm import \
                            ell_aggregate_pallas
                        from roc_tpu.kernels.graphnorm import (
                            fused_ell_aggregate_pallas,
                            indegree_norm_pallas, scale_act_pallas)
                        degj = jnp.asarray(np.concatenate(
                            [np.diff(g.row_ptr).astype(np.int32),
                             np.zeros(1, np.int32)]))
                        if mode == "fused":
                            def run_fn(x, i, p):
                                xs = indegree_norm_pallas(x, degj)
                                return fused_ell_aggregate_pallas(
                                    xs, i, p, V, dj32, act="relu")
                        else:
                            def run_fn(x, i, p):
                                y = ell_aggregate_pallas(
                                    x * dj_ext[:, None], i, p, V)
                                return jax.nn.relu(y * dj[:, None])
                        f = jax.jit(run_fn)
                        run = lambda: f(feats, idx, pos)
                    elif mode == "fused":
                        from roc_tpu.core.ell import ell_weight_tables
                        tab = ell_cache["table"]
                        w = tuple(jnp.asarray(a[0]) for a in
                                  ell_weight_tables(tab, d_np[None, :],
                                                    d_np))
                        f = jax.jit(lambda x, i, p, ww: jax.nn.relu(
                            aggregate_ell(x, i, p, V, ell_w=ww)))
                        run = lambda: f(feats, idx, pos, w)
                    else:
                        f = jax.jit(lambda x, i, p: jax.nn.relu(
                            aggregate_ell(x * dj_ext[:, None], i, p, V)
                            * dj[:, None]))
                        run = lambda: f(feats, idx, pos)
                elif base == "sectioned":
                    from roc_tpu.core.ell import sectioned_from_graph
                    from roc_tpu.ops.aggregate import aggregate_ell_sect
                    sect = sectioned_from_graph(
                        g.row_ptr, g.col_idx, V, seg_rows=args.seg_rows)
                    sidx, sdst, meta = sect.as_jax()
                    if mode == "fused":
                        w = tuple(jnp.asarray(a) for a in
                                  sect.weight_tables(d_np, d_np))
                        f = jax.jit(lambda x, i, dd, ww: jax.nn.relu(
                            aggregate_ell_sect(x, i, dd, meta, V,
                                               sect_w=ww)))
                        run = lambda: f(feats, sidx, sdst, w)
                    else:
                        f = jax.jit(lambda x, i, dd: jax.nn.relu(
                            aggregate_ell_sect(x * dj_ext[:, None], i,
                                               dd, meta, V)
                            * dj[:, None]))
                        run = lambda: f(feats, sidx, sdst)
                elif base == "bdense":
                    from roc_tpu.core.ell import sectioned_from_graph
                    from roc_tpu.ops.aggregate import aggregate_ell_sect
                    from roc_tpu.ops.blockdense import (
                        aggregate_block_dense, plan_blocks_packed)
                    min_fill = int(parts[1]) if len(parts) > 1 else 64
                    group = int(parts[2]) if len(parts) > 2 else 1
                    plan = plan_blocks_packed(
                        g.row_ptr, g.col_idx, V, min_fill=min_fill,
                        a_budget_bytes=args.a_budget or None,
                        group=group)
                    sect = sectioned_from_graph(plan.res_row_ptr,
                                                plan.res_col, V)
                    sidx, sdst, meta = sect.as_jax()
                    ab, sb, db = (jnp.asarray(plan.a_blocks),
                                  jnp.asarray(plan.src_blk),
                                  jnp.asarray(plan.dst_blk))
                    if mode == "fused":
                        dd_pad = np.zeros(plan.vpad, np.float32)
                        dd_pad[:V] = d_np
                        ddj = jnp.asarray(dd_pad)
                        w = tuple(jnp.asarray(a) for a in
                                  sect.weight_tables(d_np, d_np))

                        def run_fn(x, a, s, d, i, dd, ww):
                            y = aggregate_block_dense(
                                x, a, s, d, V, plan.vpad, group=group,
                                out_dtype=x.dtype, scale_dst=ddj,
                                scale_src=ddj)
                            return jax.nn.relu(
                                y + aggregate_ell_sect(x, i, dd, meta,
                                                       V, sect_w=ww))
                        f = jax.jit(run_fn)
                        run = lambda: f(feats, ab, sb, db, sidx, sdst, w)
                    else:
                        def run_fn(x, a, s, d, i, dd):
                            xs = x * dj_ext[:, None]
                            y = aggregate_block_dense(
                                xs, a, s, d, V, plan.vpad, group=group,
                                out_dtype=x.dtype)
                            y = y + aggregate_ell_sect(xs, i, dd,
                                                       meta, V)
                            return jax.nn.relu(y * dj[:, None])
                        f = jax.jit(run_fn)
                        run = lambda: f(feats, ab, sb, db, sidx, sdst)
                else:
                    print(f"{spec:16s} REJECTED: unknown base impl "
                          f"{base!r} for {mode}- spec")
                    continue
                prep = time.time() - t0
                ms = bench(run, args.iters)
                print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s "
                      f"(prep {prep:.1f}s)")
            except Exception as e:  # noqa: BLE001 - report and continue
                print(f"{spec:16s} FAILED: {type(e).__name__}: "
                      f"{str(e)[:200]}")
            continue
        if impl == "sectioned":
            # sectioned:ROWS overrides the section size (in source
            # rows) — the dtype-aware sweep: bf16 tables are half the
            # bytes, so sections can be 2x the rows for the same VMEM
            # footprint (fewer sections = fewer scatter passes + less
            # sub-row padding)
            from roc_tpu.core.ell import (SECTION_ROWS_DEFAULT,
                                          sectioned_from_graph)
            from roc_tpu.ops.aggregate import aggregate_ell_sect
            sec_rows = chunk if ":" in spec else SECTION_ROWS_DEFAULT
            t0 = time.time()
            sect = sectioned_from_graph(g.row_ptr, g.col_idx, V,
                                        section_rows=sec_rows,
                                        seg_rows=args.seg_rows)
            prep = time.time() - t0
            sidx, sdst, meta = sect.as_jax()
            # tables as ARGUMENTS: closure/default-arg capture embeds
            # them as HLO constants and overflows the remote-compile
            # request past ~100 MB of tables
            f = jax.jit(lambda x, i, d:
                        aggregate_ell_sect(x, i, d, meta, V))
            ms = bench(lambda: f(feats, sidx, sdst), args.iters)
            print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s "
                  f"(prep {prep:.1f}s)")
            continue
        if impl in ("sectw", "sectu16", "sectsplit"):
            # sectioned-layout variants (VERDICT r4 gather levers):
            #   sectw:W      sub-row width W instead of 8
            #   sectu16[:W]  uint16 section-local indices (section_rows
            #                65,535 so the dummy id fits), sub-row
            #                width W (default 8)
            #   sectsplit[:W] W independent [N]-index gathers instead
            #                of the [N, W] block gather
            # The :W suffix means sub-row width for ALL three variants
            # (round-4 advisor: sectu16:16 used to silently bench width
            # 8 under a width-16 label).
            from roc_tpu.core.ell import (SECTION_ROWS_DEFAULT,
                                          sectioned_from_graph)
            from roc_tpu.ops.aggregate import (aggregate_ell_sect,
                                               aggregate_ell_sect_split)
            if impl == "sectw" and ":" not in spec:
                # a bare 'sectw' measures the default width-8 config —
                # identical to 'sectioned' — and would land a mislabeled
                # row in the sweep artifact
                print(f"{spec:16s} REJECTED: 'sectw' needs an explicit "
                      f"width — use sectw:W (sectw:8 == default)")
                continue
            sub_w = chunk if ":" in spec else 8
            sec_rows = (65_535 if impl == "sectu16"
                        else SECTION_ROWS_DEFAULT)
            t0 = time.time()
            sect = sectioned_from_graph(g.row_ptr, g.col_idx, V,
                                        section_rows=sec_rows,
                                        seg_rows=args.seg_rows,
                                        sub_w=sub_w)
            if impl == "sectu16":
                sect = sect.with_idx_dtype(np.uint16)
            prep = time.time() - t0
            sidx, sdst, meta = sect.as_jax()
            agg = (aggregate_ell_sect_split if impl == "sectsplit"
                   else aggregate_ell_sect)
            f = jax.jit(lambda x, i, d, a=agg: a(x, i, d, meta, V))
            try:
                ms = bench(lambda: f(feats, sidx, sdst), args.iters)
                print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s "
                      f"(prep {prep:.1f}s, "
                      f"{sect.padded_edges/1e6:.1f}M slots)")
            except Exception as e:  # noqa: BLE001 - report and continue
                print(f"{spec:16s} FAILED: {type(e).__name__}: {e}")
            continue
        if impl == "bdense":
            # block-dense MXU path: dense [128,128] adjacency tiles as
            # bf16 batched matmuls + the residual through the sectioned
            # gather (VERDICT r4 #1).  bdense:MINFILL sets the dense
            # threshold (edges per block; default 64 ~ the measured
            # row-rate breakeven); bdense:MINFILL:GROUP reduces GROUP
            # dst-sharing blocks per output-tile update
            # (pad_plan_groups — cuts the [128,F] fp32 RMW traffic).
            # Occupancy stats print with the row — they are the
            # claim's evidence either way.
            from roc_tpu.core.ell import sectioned_from_graph
            from roc_tpu.ops.aggregate import aggregate_ell_sect
            from roc_tpu.ops.blockdense import (BLOCK,
                                                aggregate_block_dense,
                                                plan_blocks_packed)
            min_fill = chunk if len(parts) > 1 else 64
            group = int(parts[2]) if len(parts) > 2 else 1
            t0 = time.time()
            plan = plan_blocks_packed(
                g.row_ptr, g.col_idx, V, min_fill=min_fill,
                a_budget_bytes=args.a_budget or None, group=group)
            u4 = plan.a_blocks.shape[-1] == BLOCK // 2
            occ = plan.occupancy()
            res_frac = 1.0 - occ["dense_frac"]
            have_residual = plan.res_col.shape[0] > 0
            if have_residual:
                sect = sectioned_from_graph(plan.res_row_ptr,
                                            plan.res_col, V)
                sidx, sdst, meta = sect.as_jax()
            prep = time.time() - t0
            # tables as ARGUMENTS, never closure captures: captures
            # embed them as HLO constants (slow folding here, HTTP-413
            # remote-compile overflow at scale — same rule as the
            # sectioned branch above)
            ab = jnp.asarray(plan.a_blocks)
            sb = jnp.asarray(plan.src_blk)
            db = jnp.asarray(plan.dst_blk)

            if have_residual:
                def agg_bd(x, a, s, d, i, dd):
                    dense = aggregate_block_dense(x, a, s, d, V,
                                                  plan.vpad,
                                                  group=group)
                    return dense + aggregate_ell_sect(x, i, dd, meta, V)
                f = jax.jit(agg_bd)
                run = lambda: f(feats, ab, sb, db, sidx, sdst)
            else:
                f = jax.jit(lambda x, a, s, d: aggregate_block_dense(
                    x, a, s, d, V, plan.vpad, group=group))
                run = lambda: f(feats, ab, sb, db)
            try:
                ms = bench(run, args.iters)
                gpad = (f", group {group} (+{plan.pad_blocks} pad)"
                        if group > 1 else "")
                gpad += ", A u4" if u4 else ""
                print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s "
                      f"(prep {prep:.1f}s, {occ['n_blocks']} blocks, "
                      f"fill {occ['mean_fill']}, dense "
                      f"{occ['dense_frac']:.0%}, residual "
                      f"{res_frac:.0%}{gpad})")
            except Exception as e:  # noqa: BLE001 - report and continue
                print(f"{spec:16s} FAILED: {type(e).__name__}: "
                      f"{str(e)[:200]}")
            continue
        if impl == "hub":
            # hub-split: top-K most referenced sources aggregated as a
            # dense [V, K] count-matrix matmul on the MXU; the residual
            # (non-hub) edges through the sectioned gather.  Pays off
            # only on source-skewed graphs (--graph skew / real
            # power-law data); uniform sources put ~K/V of the edge
            # mass on the hubs.
            K = chunk if ":" in spec else 4096
            from roc_tpu.core.ell import sectioned_from_graph
            from roc_tpu.ops.aggregate import aggregate_ell_sect
            t0 = time.time()
            freq = np.bincount(g.col_idx, minlength=V)
            hubs = np.argsort(-freq)[:K].astype(np.int64)
            cover = float(freq[hubs].sum()) / E
            is_hub = np.zeros(V, dtype=bool)
            is_hub[hubs] = True
            hub_rank = np.zeros(V, dtype=np.int64)
            hub_rank[hubs] = np.arange(K)
            deg = np.diff(g.row_ptr)
            dst_all = np.repeat(np.arange(V, dtype=np.int64), deg)
            hub_sel = is_hub[g.col_idx]
            M = np.zeros((V, K), dtype=np.float32)
            np.add.at(M, (dst_all[hub_sel],
                          hub_rank[g.col_idx[hub_sel]]), 1.0)
            rest_col = g.col_idx[~hub_sel]
            rest_dst = dst_all[~hub_sel]
            rest_ptr = np.zeros(V + 1, dtype=np.int64)
            np.cumsum(np.bincount(rest_dst, minlength=V),
                      out=rest_ptr[1:])
            sect = sectioned_from_graph(rest_ptr, rest_col, V,
                                        seg_rows=args.seg_rows)
            prep = time.time() - t0
            sidx, sdst, meta = sect.as_jax()
            Mj = jnp.asarray(M, dtype=feats.dtype)
            hubj = jnp.asarray(hubs)

            def hub_agg(x, Mx, i, d):
                import jax as _jax
                dense = _jax.lax.dot_general(
                    Mx, x[hubj], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(x.dtype)
                return dense + aggregate_ell_sect(x, i, d, meta, V)

            f = jax.jit(hub_agg)
            try:
                ms = bench(lambda: f(feats, Mj, sidx, sdst), args.iters)
                print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s "
                      f"(prep {prep:.1f}s, hub coverage "
                      f"{cover*100:.1f}% of E)")
            except Exception as e:  # noqa: BLE001 - report and continue
                print(f"{spec:16s} FAILED: {type(e).__name__}: {e}")
            continue
        if impl == "ell":
            (idx, pos), prep = get_ell()
            f = jax.jit(lambda x, i, p: aggregate_ell(x, i, p, V))
            ms = bench(lambda: f(feats, idx, pos), args.iters)
            print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s "
                  f"(prep {prep:.1f}s)")
            continue
        if impl == "pallas":
            # the one-launch DMA kernel (kernels/ell_spmm.py), compiled
            # (not interpret) — the head-to-head VERDICT round 1 asked
            # for: same ELL tables as the XLA 'ell' row above
            from roc_tpu.kernels.ell_spmm import ell_aggregate_pallas
            (idx, pos), prep = get_ell()
            f = jax.jit(lambda x, i, p:
                        ell_aggregate_pallas(x, i, p, V))
            try:
                ms = bench(lambda: f(feats, idx, pos), args.iters)
                print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s "
                      f"(prep {prep:.1f}s)")
            except Exception as e:  # noqa: BLE001 - report and continue
                print(f"{spec:16s} FAILED: {type(e).__name__}: {e}")
            continue
        src, dst = padded_edge_list(g, multiple=chunk)
        srcj, dstj = jnp.asarray(src), jnp.asarray(dst)
        f = jax.jit(lambda x, s, d, i=impl, c=chunk:
                    aggregate(x, s, d, V, impl=i, chunk=c))
        try:
            ms = bench(lambda: f(feats, srcj, dstj), args.iters)
            print(f"{spec:16s} {ms:9.2f} ms   {gb/ms*1e3:7.1f} GB/s")
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"{spec:16s} FAILED: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()

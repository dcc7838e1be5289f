"""Block-dense occupancy census: how much of a graph's edge mass can
ride [128,128] MXU tiles under a given vertex order.

Host-side only (no accelerator): the stat that decides whether
``aggr_impl='bdense'`` can beat the ~7 ns/edge gather row-rate
(BASELINE.md "Round-5 additions").  Substrate spec is
``_substrates.py``'s, plus an optional reorder pass so the
ordering-recovery claim (core/reorder.py lpa_order) is measurable at
any scale with one command:

    python benchmarks/blockdense_occupancy.py \
        --nodes 232965 --edges 114848857 \
        --graph planted:16384 --reorder lpa

Merges the row into benchmarks/blockdense_occupancy.json under a key
derived from the spec (here ``planted16384_lpa``) so re-running the
recorded command updates the recorded row rather than forking a new
one; ``--tag`` overrides the key.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "blockdense_occupancy.json")


def main():
    from _substrates import GRAPH_SPEC_HELP, graph_from_spec, \
        reorder_graph
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=232_965)
    ap.add_argument("--edges", type=int, default=114_848_857)
    ap.add_argument("--graph", default="planted:16384",
                    help=GRAPH_SPEC_HELP)
    ap.add_argument("--reorder", default="none",
                    choices=["none", "bfs", "lpa"])
    ap.add_argument("--min-fill", type=int, default=64)
    ap.add_argument("--a-budget", type=int, default=2 << 30,
                    help="uint8 A-table byte cap (0 = uncapped)")
    ap.add_argument("--group", type=int, default=1,
                    help="pad_plan_groups alignment (the grouped "
                         "output-tile reduction); occupancy then "
                         "reports pad_blocks and the padded a_bytes")
    ap.add_argument("--pack", action="store_true",
                    help="apply the trainer's plan_blocks_packed "
                         "policy (u4 packing + 2x-budget planning) "
                         "instead of the raw uint8 plan")
    ap.add_argument("--tag", default=None,
                    help="JSON key (default: derived from the spec)")
    args = ap.parse_args()

    t0 = time.time()
    g = graph_from_spec(args.graph, args.nodes, args.edges)
    gen_s = time.time() - t0

    g, reorder_s = reorder_graph(
        g, args.reorder,
        cache_key=f"{args.graph}_{args.nodes}_{args.edges}")
    if reorder_s:
        print(f"# {args.reorder} reorder: {reorder_s:.1f}s")

    from roc_tpu.ops.blockdense import (BLOCK, plan_blocks,
                                        plan_blocks_packed)
    t0 = time.time()
    planner = plan_blocks_packed if args.pack else plan_blocks
    plan = planner(g.row_ptr, g.col_idx, g.num_nodes,
                   min_fill=args.min_fill,
                   a_budget_bytes=args.a_budget or None,
                   group=args.group)
    plan_s = time.time() - t0

    row = dict(plan.occupancy(), V=g.num_nodes, E=g.num_edges,
               min_fill=args.min_fill, gen_s=round(gen_s, 1),
               plan_s=round(plan_s, 1),
               graph=args.graph,
               reorder=args.reorder,
               reorder_s=round(reorder_s, 1))
    if args.group > 1:
        row["group"] = args.group
    if args.pack:
        row["a_u4"] = bool(plan.a_blocks.shape[-1] == BLOCK // 2)
    # non-default plan knobs join the derived key: rows measured under
    # different min_fill/a_budget must never overwrite each other
    tag = args.tag or (args.graph.replace(":", "")
                       + ("" if args.reorder == "none"
                          else f"_{args.reorder}")
                       + ("" if args.min_fill == 64
                          else f"_f{args.min_fill}")
                       + ("" if args.a_budget == 2 << 30
                          else "_bunc" if not args.a_budget
                          else f"_b{args.a_budget >> 30}g")
                       + ("" if args.group == 1 else f"_g{args.group}")
                       # suffix by the packing OUTCOME, not the knob:
                       # an unpackable graph records as '_pack' (with
                       # a_u4: false), never as a phantom u4 row
                       + ("" if not args.pack
                          else "_u4" if row["a_u4"] else "_pack"))
    print(tag, json.dumps(row, sort_keys=True))

    data = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            data = json.load(f)
    data[tag] = row
    with open(OUT, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()

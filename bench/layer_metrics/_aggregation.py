"""The model's own aggregation op, alone: shared by ``agg_ms`` and
``agg_roofline``.  Through the trainer's resolved ``gctx`` (layout,
tables and weights as the step uses them), at the widest aggregation of
the resolved model and in its compute dtype: one forward, and one
forward + vjp, each jitted alone, median of 10 calls to
``block_until_ready``.  One-chip trainers only (a distributed trainer
has no single ``gctx``: the reader then finds nothing)."""

import statistics
import time

CALLS = 10


def measure(run):
    if "aggregation" in run.scratch:
        return run.scratch["aggregation"]
    import jax
    import jax.numpy as jnp
    tr = run.trainer
    gctx = getattr(tr, "gctx", None)
    ops = [op for op in getattr(getattr(tr, "model", None), "_ops", [])
           if op.kind in ("fused_aggregate", "scatter_gather")]
    out = None
    if gctx is not None and ops:
        op = max(ops, key=lambda o: o.dim)

        def agg(x, g):
            if op.kind == "fused_aggregate":
                return g.aggregate_fused(x)
            return g.aggregate(x, op.attrs["aggr"])

        def agg_vjp(x, ct, g):
            y, pull = jax.vjp(lambda v: agg(v, g), x)
            return y, pull(ct)[0]

        V = int(gctx.num_rows)
        x = jax.random.normal(jax.random.PRNGKey(0), (V, op.dim),
                              dtype=tr.compute)

        def median_ms(fn, *args):
            jax.block_until_ready(fn(*args))
            laps = []
            for _ in range(CALLS):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                laps.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(laps)

        out = {"width": int(op.dim), "kind": op.kind,
               "itemsize": int(jnp.dtype(tr.compute).itemsize),
               "num_nodes": V,
               "num_edges": int(run.data.col_idx.shape[0]),
               "forward_ms": median_ms(jax.jit(agg), x, gctx),
               "forward_vjp_ms": median_ms(jax.jit(agg_vjp), x, x, gctx)}
    run.scratch["aggregation"] = out
    return out

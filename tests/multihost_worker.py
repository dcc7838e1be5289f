"""Worker process for the REAL 2-process DCN test.

Spawned by ``tests/test_multihost.py::test_two_process_dcn_parity`` as
two actual OS processes, each with 4 virtual CPU devices, meeting
through ``jax.distributed.initialize`` (Gloo collectives over
loopback) — the first genuine multi-address-space exercise of
``roc_tpu.parallel.multihost.init_distributed`` (the reference's
GASNet/NCCL bootstrap analog; its own multi-rank init is dead-coded,
``gnn.cc:630-642``).

Each process builds ONLY its own partitions' shards via
``shard_dataset_local``, trains 2 epochs through ``DistributedTrainer``
(gradients psum across the 8-device mesh spanning both processes),
evaluates, and predicts.  Process 0 writes metrics + final params +
logits to ``<outdir>/result.npz`` for the parent to compare against a
single-process run of the identical workload.

Usage: python multihost_worker.py <coordinator> <nproc> <pid> <outdir>
       [aggr_impl]
"""

import os
import sys


def main() -> None:
    coordinator, nproc, pid, outdir = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
    impl = sys.argv[5] if len(sys.argv) > 5 else "ell"
    # 4 virtual CPU devices per process; force CPU via jax.config (an
    # already-imported jax has latched the environment)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4")
    import jax
    jax.config.update("jax_platforms", "cpu")

    from roc_tpu.parallel import multihost as mh
    mh.init_distributed(coordinator, nproc, pid)
    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.local_devices()) == 4

    import numpy as np
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.core.partition import partition_graph
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig

    n_parts = 4 * nproc
    ds = synthetic_dataset(16 * n_parts, 6, in_dim=12, num_classes=3,
                           seed=0)
    mesh = mh.make_parts_mesh(n_parts)
    local = mh.process_local_parts(mesh)
    # locality layout: this process owns a contiguous block of 4 parts
    assert len(local) == 4, local
    # min_fill=8 for bdense: the tiny fixture must actually yield
    # dense tiles so the cross-process block-count agreement is real
    cfg = TrainConfig(epochs=2, verbose=False, aggr_impl=impl,
                      bdense_min_fill=8,
                      symmetric=True, dropout_rate=0.0,
                      eval_every=1 << 30)
    pg = partition_graph(ds.graph, n_parts, node_multiple=8,
                         edge_multiple=cfg.chunk)
    data = mh.shard_dataset_local(ds, pg, mesh, aggr_impl=impl,
                                  bdense_min_fill=8)
    tr = DistributedTrainer(build_gcn([12, 8, 3], dropout_rate=0.0),
                            ds, n_parts, cfg, mesh=mesh, data=data,
                            pg=pg)
    tr.train(epochs=2)
    m = tr.evaluate()
    logits = tr.predict()
    if pid == 0:
        out = {f"param_{k}": np.asarray(v) for k, v in tr.params.items()}
        out["logits"] = logits
        out["train_loss"] = np.float64(m["train_loss"])
        out["train_acc"] = np.float64(m["train_acc"])
        np.savez(os.path.join(outdir, "result.npz"), **out)
    print(f"WORKER_OK pid={pid}", flush=True)


if __name__ == "__main__":
    main()

"""Worker process for the serve warm-start tests (ISSUE 11).

Simulates a COLD server deployment: loads a predictor from an exported
artifact (no trainer, no dataset for the precomputed backend), warms
its program set against the persistent cache a previous export process
populated (asserting every program is a warm hit), starts the
microbatch server, and answers queries.  The parent asserts, from the
events artifact and the cache directory, that this process compiled
ZERO new serve programs and that its compile events' program_key set
matches the artifact manifest exactly.

Usage: python serve_worker.py <artifact_dir>
Env:   JAX_COMPILATION_CACHE_DIR (cache), ROC_TPU_EVENTS (events JSONL),
       ROC_TPU_CACHE_MIN_SECS=0 (persist everything).
"""

import json
import sys


def main() -> None:
    art = sys.argv[1]
    from roc_tpu.analysis import force_cpu_rig
    force_cpu_rig()

    from roc_tpu.utils.compile_cache import enable_compile_cache
    d = enable_compile_cache()   # dir + min-secs from env
    assert d, "cache dir must be usable in the worker"

    from roc_tpu.serve.export import load_predictor
    from roc_tpu.serve.server import Server
    pred = load_predictor(art)
    # first-query readiness check: the artifact's programs must all be
    # warm hits against the cache the export populated
    warm = pred.warm(name="serve_worker")
    assert warm["compile_cold"] == 0, warm
    assert warm["compile_warm_hits"] == warm["programs"], warm
    with Server(pred, max_wait_ms=2.0) as srv:
        futs = [srv.submit([i, i + 1]) for i in range(0, 40, 2)]
        rows = [f.result() for f in futs]
        assert all(r.shape[0] == 2 for r in rows)
        stats = srv.stats()
    man = json.load(open(f"{art}/serve_manifest.json"))
    print("WORKER_OK "
          + json.dumps({"n_batches": stats["n_batches"],
                        "programs": len(man["program_keys"])}),
          flush=True)


if __name__ == "__main__":
    main()

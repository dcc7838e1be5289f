"""Worker process for the prewarm correctness tests (ISSUE 7).

Runs ONE live rig lifecycle (train 1 epoch + evaluate + predict) in a
fresh process against a persistent compile cache a previous prewarm
process populated.  The parent asserts, from the events artifact and
the cache directory, that the warm process compiled ZERO new step
programs: its ``compile`` events' program_key set equals the auditor's
enumeration, and no new step-program entry appeared in the cache.

Usage: python prewarm_worker.py <rig_name>
Env:   JAX_COMPILATION_CACHE_DIR (cache), ROC_TPU_EVENTS (events JSONL),
       ROC_TPU_CACHE_MIN_SECS=0 (persist everything).
"""

import sys


def main() -> None:
    name = sys.argv[1]
    from roc_tpu.analysis import force_cpu_rig
    force_cpu_rig()

    from roc_tpu.utils.compile_cache import enable_compile_cache
    d = enable_compile_cache()   # dir + min-secs from env
    assert d, "cache dir must be usable in the worker"

    from roc_tpu.analysis.programspace import (build_rig_dataset,
                                               build_rig_trainer,
                                               rig_configs)
    spec = rig_configs()[name]
    tr = build_rig_trainer(spec, build_rig_dataset())
    tr.train(1)
    m = tr.evaluate()
    logits = tr.predict()
    assert logits.shape[0] == 256, logits.shape
    print(f"WORKER_OK loss={m['train_loss']:.4f}", flush=True)


if __name__ == "__main__":
    main()

"""Persistent XLA compilation cache.

The reference pays no compilation cost (hand-written CUDA kernels);
the JAX rebuild's one-time cost is XLA compilation of the jitted step,
fresh per process.  JAX's persistent cache keyed on (HLO, compiler
version, device kind) removes that for every process after the first.
Enabled by default in the CLI and the benchmark harnesses; library
users opt in by calling this before the first jit.

Where the cache lives — ONE rule, one variable:

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and the code sets
  no other — it wins over an explicit ``cache_dir`` argument and every
  ``--cache-dir`` flag (the operator placed the cache from outside).
- not set: the explicit ``cache_dir`` argument, else
  ``<repo>/.jax_cache`` — a fixed path inside the checkout (the path is
  part of the cache key, so never a temp name, a pid or a time).
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """The directory :func:`enable_compile_cache` will use (see the
    module docstring for the precedence)."""
    return os.environ.get(ENV_VAR) or cache_dir or DEFAULT_DIR


def enable_compile_cache(cache_dir: Optional[str] = None,
                         min_compile_secs: Optional[float] = None
                         ) -> str:
    """Point JAX's persistent compilation cache at
    :func:`resolve_cache_dir` and return the directory.  Safe to call
    any time before the first compilation.  A directory that cannot be
    created raises — a run that was asked to cache does not silently
    go on uncached.

    ``min_compile_secs`` is the write threshold: programs whose
    compile is faster are NOT persisted.  ``None`` defers to
    $ROC_TPU_CACHE_MIN_SECS, else 1.0 s — which skips the many small
    per-block streamed-head programs, so the prewarm driver
    (utils/prewarm.py) passes 0.0 explicitly
    (TrainConfig.cache_min_compile_secs / --cache-min-secs expose it
    to users)."""
    import jax
    if min_compile_secs is None:
        min_compile_secs = float(
            os.environ.get("ROC_TPU_CACHE_MIN_SECS", 1.0))
    d = resolve_cache_dir(cache_dir)
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    # The persistent cache object is created once, on the first
    # compilation after it's configured — a later config update alone
    # does NOT re-point an already-initialized cache (observed: the
    # CLI's default-dir cache swallowing a later explicit dir in the
    # same process).  Dropping the instance makes the next compile
    # re-initialize against the directory just configured.
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    return d

"""Test configuration: force an 8-virtual-device CPU platform BEFORE jax
initializes, so sharding tests run anywhere (SURVEY.md §4 test plan)."""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# the compile cache location must not depend on the caller's
# environment (utils/compile_cache.py: this variable wins over every
# explicit directory); cleared BEFORE jax import, which also reads it
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# crash-flight-recorder dumps (obs/events.py) from in-process tests
# must never land in the repo root: pin the dump dir to a scratch
# location unless a test overrides it
os.environ.setdefault(
    "ROC_TPU_FLIGHT_DIR", tempfile.mkdtemp(prefix="roc_flight_"))
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test")

# Pin the platform in jax's own config as well: an already-imported
# jax has latched the environment, and the 8 virtual devices only
# exist on the CPU platform.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

"""Test configuration: run the whole suite on an 8-device virtual CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): multi-device tests run
without a cluster by faking devices on one host
(xla_force_host_platform_device_count), the way the reference runs dist
kvstore tests with local worker/server processes.

Persistent compilation cache (docs/compilation.md): cold XLA compiles
dominate the tier-1 wall-clock budget, so the session turns on jax's
persistent cache at the directory the framework itself resolves
(`mxnet_tpu.compile.cache.resolve_cache_dir`: JAX_COMPILATION_CACHE_DIR,
then MXTPU_COMPILE_CACHE / MXTPU_XLA_CACHE, then <repo>/.jax_cache) — the
second run of the suite (and every subprocess test inside any run, which
resolves the same directory) reloads executables instead of recompiling
them. MXTPU_COMPILE_CACHE=0 opts out.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")
# importing the package starts no backend; enable_cache also installs
# the multi-device read guard (a cache-deserialized multi-device CPU
# executable can segfault jaxlib — see compile/cache.py) before anything
# in the session compiles
from mxnet_tpu.compile.cache import enable_cache

enable_cache()

import pytest


@pytest.fixture(autouse=True)
def _detach_step_trace():
    """A trainer's step root stays current on its thread between steps
    (observability/trace.StepRoot); a test's last step must not parent
    the next test's spans."""
    yield
    from mxnet_tpu.observability import trace
    trace.detach()

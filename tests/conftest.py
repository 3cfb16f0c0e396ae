"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference has no simulated-cluster story (SURVEY §4 — it always requires
real GPUs); JAX gives us one: ``--xla_force_host_platform_device_count``.
The chip is reached only through ``chip_smoke.py``; nothing here touches it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from galvatron_tpu.aot.cache import enable_persistent_cache, resolve_compile_cache_dir
from galvatron_tpu.aot.warmup import force_cpu_world

force_cpu_world(8)

import jax

# Persistent compilation cache: the suite is compile-bound (every pipeline
# test builds fresh shard_map programs); caching compiled executables across
# test processes cuts re-run wall time drastically. ONE shared wiring and
# placement (aot/cache.py: JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)
# — the same the trainer, `cli warmup` and chip_smoke.py use;
# min_compile_time 0.5s keeps thousands of trivial test programs from
# churning the cache dir.
enable_persistent_cache(resolve_compile_cache_dir(), min_compile_time_s=0.5)

import pytest


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert len(jax.devices()) == 8, "tests expect the 8-device CPU simulation"

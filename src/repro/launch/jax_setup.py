"""Process-level JAX setup shared by the launchers and ``chip_smoke.py``.

Both helpers must run before the process's first computation: the
host-device count is read when the CPU backend starts. This module
imports jax only inside :func:`enable_compile_cache`, so calling
:func:`force_cpu_devices` first is always safe.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

# <repo>/.jax_cache (gitignored): a fixed path, so that a later process
# in the same checkout finds what an earlier one compiled
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    keeps its cache there and nothing is changed; otherwise the cache
    goes to :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def force_cpu_devices(n: int) -> bool:
    """Give the CPU backend ``n`` host devices so that an ``n``-shard
    mesh exists on a host without an accelerator — only when the CPU
    platform is requested (``JAX_PLATFORMS=cpu``); on any other
    platform the mesh is built over the real devices and nothing is
    set. Keeps the rest of ``XLA_FLAGS`` but replaces an inherited
    device count (a smaller one would make the mesh build fail).
    Returns whether the count was set."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return False
    cur = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                 os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        f"{cur} --xla_force_host_platform_device_count={n}").strip()
    return True

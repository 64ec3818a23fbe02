"""Process start-up on whatever device JAX came up on.

Two things every entry point (CLI, server, benchmarks/run.py,
__graft_entry__.py, tests/conftest.py) needs before its first compile, kept in one place:

- where compiled programs persist. A cold 7B serving process spends
  minutes compiling; the cache directory is part of the cache key, so it
  must not move between runs. ``JAX_COMPILATION_CACHE_DIR`` (which JAX
  reads by itself) places it from outside; otherwise it is
  ``<checkout>/.jax_cache``, resolved from this package's own location.
- which device that is, as JAX reports it — /health and every benchmark
  result carry it so a process that silently came up on the CPU of a chip
  machine cannot pass for a chip run.
"""

from __future__ import annotations

import os
from pathlib import Path

_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache at a fixed directory.
    Call once before the first compile. With ``JAX_COMPILATION_CACHE_DIR``
    set no directory is set here: JAX already honours the variable.

    Every program is cached, not only the slow compiles: a second run
    against the same directory then adds no entries, which is how a
    recompile shows up from outside the process."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them (initializes the backend on first use)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def device_memory_in_use() -> dict[str, int]:
    """``bytes_in_use`` per local device, for the devices whose backend
    reports memory stats (the CPU backend reports none)."""
    import jax

    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and "bytes_in_use" in stats:
            out[str(d.id)] = int(stats["bytes_in_use"])
    return out

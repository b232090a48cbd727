"""Which JAX backend a process serves on, and where it keeps compiled
programs.

JAX decides the backend, once, in the process that serves: there is no
probe subprocess and no fallback. A chip belongs to one process at a
time, so a process that has called `backend_info()` (or any other JAX
device op) must not start a child that initialises a backend too.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — fixed, because the directory is part of the
# cache key: a path that moves (tempfile, pid, timestamp) never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def force_cpu_backend() -> None:
    """Pin jax to the CPU platform (`--no-device`). Must run before the
    process's first device use: a backend, once initialised, stays."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def backend_info() -> dict:
    """The backend JAX gives THIS process: platform, device kind and
    device count, as `jax.devices()` reports them. The first call
    initialises the backend — and so takes the chip — and raises JAX's
    own error when that fails. What the server serves on is what this
    says (boot log line + `/__pingoo/metrics` JSON)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a placeable, fixed
    directory and return it. `JAX_COMPILATION_CACHE_DIR`, when set, is
    read by JAX itself and nothing is set in code; otherwise the cache
    lives at DEFAULT_COMPILE_CACHE_DIR. Call before anything jits. The
    cache thresholds stay at JAX's defaults.

    The cache's key covers the operations' NAMES and not their source
    lines. By default JAX strips all debug info from the key, so a
    cache filled before a `jax.named_scope` existed (engine/verdict.py's
    device-trace vocabulary) would go on serving programs without it —
    a profiler trace taken after an upgrade would name nothing (met on
    the chip, PR 28). With the names in the key such a program compiles
    once more; with the tracebacks out of the locations, moving code
    does not."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR

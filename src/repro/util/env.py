"""Computation-environment setup that must happen BEFORE jax initializes.

The multi-device SNN path (DESIGN.md §15) runs on plain CPU hosts by
simulating a device mesh: XLA splits the host into ``N`` logical devices
when ``--xla_force_host_platform_device_count=N`` is in ``XLA_FLAGS`` at
backend-initialization time.  That flag is process-global and read once,
so every entry point that wants a mesh -- tests (tests/conftest.py),
benchmarks (benchmarks/run.py, bench_snn_scale.py), the serve CLI and CI
-- funnels through :func:`ensure_host_device_count` instead of each
hand-rolling the ``os.environ`` dance (launch/dryrun.py predates this
module and keeps its subprocess-env variant).

Importing :mod:`jax` does NOT initialize the backend -- the first device
lookup or op does -- so calling these from a ``main()`` after imports is
fine; calling them after the first jax op is a silent no-op on the flag,
which is why :func:`ensure_host_device_count` returns the *actual*
device count for the caller to check.
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache: a fixed path, because the path is part of what the
# cache is keyed on -- a directory that moved between runs never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    this sets no other directory.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  Call it from a ``main()``, never at
    import: a library import must not redirect the caller's cache.
    """
    if os.environ.get(_CACHE_ENV):
        return os.environ[_CACHE_ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def ensure_host_device_count(n: int) -> int:
    """Ask XLA for ``n`` simulated host devices; return the actual count.

    Appends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    (leaving any other flags intact) unless some value for it is already
    set -- an explicit operator/dry-run choice wins.  Then imports jax
    (initializing the backend if this is the first touch) and returns
    ``len(jax.devices())``, which callers must treat as the truth: if the
    backend initialized before this call, the flag had no effect and the
    return value says so.
    """
    if int(n) < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={int(n)}".strip()
    import jax

    return len(jax.devices())


def set_platform(platform: str = "cpu") -> None:
    """Pin the jax platform ("cpu" | "gpu" | "tpu"); effective only before
    the first jax op of the process (same contract as the XLA flag)."""
    import jax

    jax.config.update("jax_platform_name", platform)


def enable_x64(use_x64: bool = True) -> None:
    """Toggle 64-bit array defaults (the repo's programs are f32-strict --
    see repro.analysis -- so this exists for host-side verification
    scripts, not for anything that lowers)."""
    import jax

    jax.config.update("jax_enable_x64", bool(use_x64))

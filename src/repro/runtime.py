"""Process-level runtime setup shared by the entry points.

Nothing here imports jax at module level: :func:`virtual_cpu_devices` must
run before the first jax import, and the other helpers import it lazily.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing is set here.  Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (the path is part of the cache key, so it never
    moves between runs)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def virtual_cpu_devices(n: int) -> None:
    """Ask XLA for ``n`` virtual host devices — only when the process is
    pinned to the CPU (``JAX_PLATFORMS=cpu``).  On a machine with chips the
    mesh is built from real devices (:func:`first_devices`) instead.  Must
    run before jax is imported."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))


def first_devices(n: int) -> list:
    """The first ``n`` devices of the default backend (the chips on a TPU
    host, the virtual devices of a CPU-pinned process); raises when the
    process has fewer."""
    import jax

    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, found {len(devices)} "
            f"({devices[0].platform}); on a CPU host run with "
            "JAX_PLATFORMS=cpu to get virtual devices")
    return devices[:n]

"""Process-level platform choices, each made in exactly one place.

- the CPU test helpers (:func:`force_host_device_count`, :func:`pin_cpu`);
- whether Pallas kernels are compiled or interpreted
  (:func:`pallas_interpret`);
- where the persistent compilation cache lives
  (:func:`enable_compilation_cache`).

Nothing here probes for a device or falls back to another one: the
program runs on the platform JAX gives it, and an entry point that needs a
TPU checks ``jax.devices()[0].platform`` itself and fails when it is not.
"""

from __future__ import annotations

import os
import re

__all__ = ["enable_compilation_cache", "force_host_device_count",
           "pallas_interpret", "pin_cpu"]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_host_device_count(n: int) -> None:
    """Set (or raise to ``n``) ``--xla_force_host_platform_device_count``.

    Only effective before this process initializes a JAX backend.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    pat = r"--xla_force_host_platform_device_count=(\d+)"
    m = re.search(pat, flags)
    if m:
        if int(m.group(1)) < n:
            flags = re.sub(
                pat, f"--xla_force_host_platform_device_count={n}", flags)
            os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def pin_cpu() -> None:
    """Pin the CPU platform (env + config) before backend init; harmless
    after (``jax.devices("cpu")`` keeps working either way)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend may already be initialized


def pallas_interpret() -> bool:
    """The ``interpret=`` argument of every ``pl.pallas_call`` in the repo.

    On a TPU the kernels are compiled by Mosaic; on the CPU (the test
    mesh) they run in the Pallas interpreter.  Any other backend is an
    error: no kernel here was written for it, and interpreting silently
    would hide that a TPU was expected and not found.
    """
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"apex_tpu Pallas kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default JAX backend is {backend!r}")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set in code, so whoever runs the program places the
    cache.  Otherwise the cache sits at one fixed path inside the checkout
    (``bench_results/.xla_cache``, git-ignored, made here): the path is part of the
    cache key, so a directory that moves between runs never hits.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_REPO, "bench_results", ".xla_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir

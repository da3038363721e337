"""The one place that configures JAX for this repository's programs.

``configure_jax()`` is called before first device use by every entry
point that runs JAX work (``chip_smoke.py``, ``benchmarks.common``, the
trace-tier lint).  It sets no platform: tests force the CPU with
``JAX_PLATFORMS=cpu``; on a TPU host JAX picks the chip by itself.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed cache location when ``JAX_COMPILATION_CACHE_DIR`` is unset
#: (a fixed path, because the path is part of the cache's key)
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"

_CONFIGURED = False


def configure_jax() -> str:
    """Turn on the persistent compilation cache and pin the XLA *host*
    platform to one device (the engines vectorize with ``vmap``; extra
    host devices only split the CPU).  Returns the cache directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it itself,
    so no directory is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Idempotent; the XLA flag is a no-op once
    the backend is initialized.
    """
    global _CONFIGURED
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache = env or str(CHECKOUT_CACHE)
    if _CONFIGURED:
        return cache
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=1").strip()
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    import jax
    if not env:
        jax.config.update("jax_compilation_cache_dir", cache)
    # cache everything: the scan kernel is cheap to serialize and the
    # point is skipping its compile in a fresh process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _CONFIGURED = True
    return cache

"""Which device the process runs on, and where its compiled programs persist.

Nothing here runs at import time: the launchers (``repro.launch.serve.main``
and ``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout (git-ignored): the cache directory is part
# of what makes a cached program found again, so it never moves
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_report() -> dict:
    """``platform``, ``kind`` and ``count`` of the devices JAX sees."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}

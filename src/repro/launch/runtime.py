"""Process start-up shared by the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/run.py``): where compiled programs
are cached, and which devices the run is on.

Nothing here runs at import; each entry point calls the helpers first
thing in its ``main``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (git-ignored): the cache key includes
# the directory, so a path derived from a temp name, pid or time never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``DEFAULT_CACHE_DIR``.
    The only place in the repo that sets a cache directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_info() -> dict:
    """The devices as JAX reports them: platform, device kind, count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_line() -> str:
    """One printable line naming the devices; entry points print it early
    so every result they print is tied to the hardware it ran on."""
    info = device_info()
    return (f"device: platform={info['platform']} kind={info['kind']} "
            f"count={info['count']}")

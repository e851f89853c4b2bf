"""Process-level set-up shared by the entry points.

* :func:`enable_compile_cache` turns on JAX's persistent compilation
  cache.  The entry points call it before their first compile (never at
  import, so tests and library users keep the cache off).
* :func:`device_info` names the device every reported rate came from, so a
  CPU number cannot pass for a chip number.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path inside the checkout (git ignores it): a cache whose
# directory moves from run to run never hits
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache -> its directory.

    ``JAX_COMPILATION_CACHE_DIR`` names the directory when it is set (JAX
    reads it itself; no other is set here); otherwise the cache lives in
    ``<checkout>/.jax_cache``.  Every compile is cached, however short: a
    cold run compiles one kernel per bucket shape, each in a second or two.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX runs on."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_tag() -> str:
    """One-line form of :func:`device_info` for report lines."""
    d = device_info()
    return f"[{d['platform']} {d['kind']} x{d['count']}]"

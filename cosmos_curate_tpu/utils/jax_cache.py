"""Persistent XLA compilation cache, enabled once per process.

Model stages construct their own jit closures, so a fresh process (or a
fresh model instance whose ``init`` is traced anew) pays full XLA
compilation even for programs compiled seconds earlier by a warmup in the
same session. The persistent cache turns every repeat compile — across
processes, across runs, across the bench's warmup/measure split — into a
disk hit. The reference has no analogue (CUDA kernels ship precompiled);
on TPU this is the idiomatic fix for XLA's compile-once-per-process model.

One cache, placeable from outside: where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX itself reads it and this module sets no directory in code. Where
it is unset the cache is ``.jax_cache`` at the root of the checkout — a
fixed path (the path is part of a cache entry's key, so a directory that
moves never hits). ``CURATE_COMPILE_CACHE=0`` turns the cache off.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

_LOCK = threading.Lock()
_ENABLED = False

# "0"/"off" disables the persistent cache entirely; anything else (or
# unset) leaves it on. WHERE it lives is JAX_COMPILATION_CACHE_DIR's job.
COMPILE_CACHE_ENV = "CURATE_COMPILE_CACHE"
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def cache_dir() -> str | None:
    """The cache directory in use, or None when the knob turns it off."""
    if os.environ.get(COMPILE_CACHE_ENV, "").strip().lower() in ("0", "off", "false", "no"):
        return None
    return os.environ.get(JAX_CACHE_DIR_ENV) or CHECKOUT_CACHE_DIR


def enable_persistent_cache() -> str | None:
    """Idempotently turn the persistent compilation cache on.

    Must run before the first compile to capture it; callers at natural
    chokepoints (registry.load_params, DevicePipeline construction,
    chip_smoke) make that true for every model path. Returns the cache dir
    in use, or None when CURATE_COMPILE_CACHE disables the cache.
    """
    global _ENABLED
    path = cache_dir()
    with _LOCK:
        if _ENABLED:
            return path
        import jax

        if path is None:
            jax.config.update("jax_enable_compilation_cache", False)
        else:
            if not os.environ.get(JAX_CACHE_DIR_ENV):
                # only the in-checkout default is set in code: a directory
                # given from outside is JAX's own to read
                jax.config.update("jax_compilation_cache_dir", path)
            # Default min compile time is 1s; embed/caption programs compile
            # in 0.5-40s, so lower the floor to catch the small-but-repeated
            # ones.
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
        _ENABLED = True
    return path

"""One persistent XLA compile cache for every entry point.

`simulate`, the serve path, `FleetRunner` and `chip_smoke.py` all call
`enable_compile_cache()`. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads
it by itself and nothing is set here. Otherwise the cache lives at one fixed
path inside the checkout, `<repo>/.jax_cache/`: the path is part of what a
later process matches on, so it never moves between runs.
"""
from __future__ import annotations

import os
import pathlib
import threading

import jax
from jax.experimental.compilation_cache import compilation_cache

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
_lock = threading.Lock()


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its one directory; returns it."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = str(DEFAULT_DIR)
    with _lock:
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
            # JAX latches "no cache" on the first compile of the process,
            # which import-time constants often trigger; reset so the next
            # compile reads the directory.
            compilation_cache.reset_cache()
    return path


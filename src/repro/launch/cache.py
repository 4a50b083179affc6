"""JAX's persistent compilation cache for the command-line entry points.

Compiling the round program and its kernels takes a large share of a cold
run on the chip, so every entry point (``chip_smoke.py``, the
``repro.serve.server`` and ``repro.net.server`` processes, ``launch/*``)
turns the cache on before it first touches a device.  Nothing turns it on
at import.
"""

from __future__ import annotations

import os
import pathlib

# src/repro/launch/cache.py → the checkout root.
_REPO = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache is ``<repo>/.jax_cache``:
    one fixed path, because a later run finds an entry only under the path
    it was written to.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(_REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

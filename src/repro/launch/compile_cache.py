"""Where JAX's persistent compilation cache lives.

Every entry point (``launch/train.py``, ``launch/serve.py``, the
``benchmarks/*.py`` mains, ``chip_smoke.py``) calls
``configure_compile_cache()`` first.  A cache is found again only under
the same path, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that variable itself, and nothing is
set here), else ``<checkout>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

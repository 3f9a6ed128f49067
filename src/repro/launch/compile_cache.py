"""Where JAX keeps compiled programs between processes.

A cold start of the server compiles tens of seconds of XLA and Mosaic
programs; the persistent cache lets the next process on the same machine
skip that.  The cache key includes the directory, so it must not move:
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself and
this module sets nothing), otherwise the cache lives at one fixed path in
the checkout.  Entry points call ``enable_compile_cache()`` first thing,
before anything compiles; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path

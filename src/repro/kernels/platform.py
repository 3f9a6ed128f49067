"""Where the Pallas kernels run, decided from the backend alone.

On a TPU backend every kernel compiles with Mosaic; a kernel that Mosaic
refuses raises there, it never falls back to the interpreter or to its
``ref.py`` oracle.  On any other backend the kernels run in Pallas's
interpreter.  The ops wrappers ask here; no caller passes a flag.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"

"""Jit'd wrapper: pad N, run fused intersection, return (mask, count)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.platform import on_tpu
from repro.kernels.twin_probe.kernel import twin_probe_pallas


@partial(jax.jit, static_argnames=("tol", "bn"))
def twin_probe(probe_rows: jax.Array, sims0: jax.Array, *,
               tol: float = 1e-6, bn: int = 512
               ) -> tuple[jax.Array, jax.Array]:
    """(c, N) unsorted probe rows + (c,) probe sims -> Set_0 mask (N,) and
    |Set_0| count (the n/125 overflow check input)."""
    c, N = probe_rows.shape
    pad = (-N) % bn
    # Sentinel-pad so padded columns never match (sims live in [-1, 1]).
    rows = jnp.pad(probe_rows, ((0, 0), (0, pad)), constant_values=-3.0)
    mask, counts = twin_probe_pallas(rows, sims0, tol, bn=bn,
                                     interpret=not on_tpu())
    return mask[:N, 0], jnp.sum(counts)

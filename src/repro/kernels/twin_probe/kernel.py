"""Pallas TPU kernel: fused probe-interval intersection (Algorithm 1 line 9).

Given the c probes' similarity rows (user-id order) and the new user's
probe similarities, a user x is a Set_0 candidate iff
``|S[i, x] − s0_i| ≤ tol`` for every probe i.  The kernel streams (c, bn)
blocks through VMEM and emits both the AND-reduced candidate mask and a
per-block candidate count (the |Set_0| ≤ n/125 overflow check) in one pass
— the (c, N) boolean intermediate and the separate count reduction never
reach HBM.

c is small (the paper uses c ≪ n/125; we default 8) so the block working
set is c·bn·4 bytes ≈ 16 KB at bn=512.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(tol: float):
    def kernel(rows_ref, s0_ref, mask_ref, count_ref):
        blk = rows_ref[...]                              # (c, bn)
        s0 = s0_ref[...]                                 # (c, 1)
        hit = jnp.abs(blk - s0) <= tol
        mask = jnp.all(hit, axis=0)                      # (bn,)
        mask_ref[...] = mask[:, None]
        count_ref[...] = jnp.sum(mask.astype(jnp.int32))[None, None]
    return kernel


def twin_probe_pallas(probe_rows: jax.Array, sims0: jax.Array,
                      tol: float = 1e-6, *, bn: int = 512,
                      interpret: bool
                      ) -> tuple[jax.Array, jax.Array]:
    """probe_rows: (c, N) unsorted probe similarity rows; sims0: (c,).
    Returns (mask (N, 1) bool, per-block counts (N/bn, 1) int32)."""
    c, N = probe_rows.shape
    assert N % bn == 0, (N, bn)
    grid = (N // bn,)
    mask, counts = pl.pallas_call(
        _make_kernel(tol),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, bn), lambda j: (0, j)),
            pl.BlockSpec((c, 1), lambda j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bn, 1), lambda j: (j, 0)),
            pl.BlockSpec((1, 1), lambda j: (j, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((N, 1), jnp.bool_),
            jax.ShapeDtypeStruct((N // bn, 1), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(probe_rows, sims0[:, None])
    return mask, counts

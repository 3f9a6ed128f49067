"""Jit'd wrapper for the verification kernel (pad + run + squeeze)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.platform import on_tpu
from repro.kernels.verify_rows.kernel import verify_rows_pallas


@jax.jit
def rows_sorted_finite(vals: jax.Array, n_active: jax.Array) -> jax.Array:
    """(R, L) per-row invariant flags: live rows must be finite and
    ascending.  The serving layer's cheap poison detector — one fused
    reduction over the arena, same row-major streaming access pattern as
    the verification kernel (verify_rows checks candidate rows against a
    target; this checks every row against its own ordering contract)."""
    R = vals.shape[0]
    live = jnp.arange(R, dtype=jnp.int32) < n_active
    finite = jnp.all(jnp.isfinite(vals), axis=1)
    ascending = jnp.all(jnp.diff(vals, axis=1) >= 0, axis=1)
    return (finite & ascending) | ~live


@jax.jit
def arena_healthy(sim_vals: jax.Array, ratings: jax.Array,
                  norms: jax.Array, n_active: jax.Array) -> jax.Array:
    """() bool — the whole-arena NaN/ordering invariant the snapshot and
    rollback machinery keys on: live similarity lists sorted ascending with
    no non-finite values, live rating rows and norms finite, ``n_active``
    within capacity."""
    R = ratings.shape[0]
    live = jnp.arange(R, dtype=jnp.int32) < n_active
    lists_ok = jnp.all(rows_sorted_finite(sim_vals, n_active))
    ratings_ok = jnp.all(jnp.all(jnp.isfinite(ratings), axis=1) | ~live)
    norms_ok = jnp.all((jnp.isfinite(norms) & (norms >= 0)) | ~live)
    n_ok = (n_active >= 0) & (n_active <= R)
    return lists_ok & ratings_ok & norms_ok & n_ok


@partial(jax.jit, static_argnames=("bs", "bk"))
def verify_rows(C: jax.Array, r0: jax.Array, valid: jax.Array, *,
                bs: int = 256, bk: int = 512) -> jax.Array:
    """(s, m) candidates vs (m,) target -> (s,) bool verified-twin flags."""
    s, m = C.shape
    ps, pk = (-s) % bs, (-m) % bk
    Cp = jnp.pad(C, ((0, ps), (0, pk)))
    # Padded item columns must match on padded rows too: r0 pads with zeros,
    # matching C's zero padding, so equality is preserved.
    r0p = jnp.pad(r0, (0, pk))
    vp = jnp.pad(valid, (0, ps))            # padded rows -> invalid
    out = verify_rows_pallas(Cp, r0p, vp, bs=bs, bk=bk,
                             interpret=not on_tpu())
    return out[:s, 0]

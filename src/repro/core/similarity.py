"""Similarity measures for neighbourhood-based CF.

All measures are exposed in two forms:
  * ``*_matrix(R)``  — full pairwise similarity (the O(n^2 m) build);
  * ``*_vs_all(R, norms, r0)`` — one new row against every existing row (the
    O(n m) traditional per-user path the paper's TwinSearch displaces).

Zero entries mean "unrated".  Cosine (the paper's benchmark metric) reduces
to normalised matmuls, which is also what the Pallas kernel in
``repro/kernels/similarity`` implements; Pearson over the co-rated support is
expressed exactly with four matmuls so it stays MXU-friendly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12
# TwinSearch matches similarities across lists to 1e-6, so every product
# runs at f32 accuracy; the TPU's default matmul precision rounds f32
# operands to bf16 (about 3 significant digits).
HIGHEST = jax.lax.Precision.HIGHEST


def row_norms(R: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.sum(jnp.square(R.astype(jnp.float32)), axis=-1))


def _safe(x: jax.Array) -> jax.Array:
    return jnp.maximum(x, EPS)


# ---------------------------------------------------------------------------
# Cosine (the paper's metric)
# ---------------------------------------------------------------------------

def cosine_matrix(R: jax.Array, *, compute_dtype=jnp.float32) -> jax.Array:
    """(n, n) cosine similarity; fp32 accumulation."""
    Rn = R.astype(compute_dtype) / _safe(row_norms(R))[:, None].astype(compute_dtype)
    return jnp.einsum("im,jm->ij", Rn, Rn, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def cosine_vs_all(R: jax.Array, norms: jax.Array, r0: jax.Array) -> jax.Array:
    """(n,) cosine similarity of one new row ``r0`` against every row of R.

    ``norms`` is the cached row-norm vector (0 for inactive rows: their
    similarity is reported as 0 and must be masked by the caller).
    """
    r0 = r0.astype(jnp.float32)
    dots = jnp.einsum("nm,m->n", R.astype(jnp.float32), r0,
                      precision=HIGHEST, preferred_element_type=jnp.float32)
    denom = _safe(norms) * _safe(jnp.linalg.norm(r0))
    return dots / denom


# ---------------------------------------------------------------------------
# Pearson over the co-rated support (exact, matmul form)
# ---------------------------------------------------------------------------

def pearson_matrix(R: jax.Array) -> jax.Array:
    """Pearson correlation restricted to co-rated items, computed exactly via
    matmuls:  with B = (R != 0),

      n_co      = B  @ B.T
      sum_uv    = R  @ R.T          (non-co terms vanish: 0 * r = 0)
      sum_u|v   = R  @ B.T          (row sums over the co-support)
      sq_u|v    = R^2 @ B.T

      cov  = sum_uv - sum_u * sum_v / n_co
      var_u = sq_u - sum_u^2 / n_co   (and symmetrically for v)
    """
    Rf = R.astype(jnp.float32)
    B = (Rf != 0).astype(jnp.float32)
    n_co = B @ B.T
    sum_uv = Rf @ Rf.T
    sum_u = Rf @ B.T               # sum of u's ratings over co-support with v
    sq_u = jnp.square(Rf) @ B.T
    n_safe = _safe(n_co)
    cov = sum_uv - sum_u * sum_u.T / n_safe
    var_u = sq_u - jnp.square(sum_u) / n_safe
    var_v = var_u.T
    sim = cov / _safe(jnp.sqrt(_safe(var_u) * _safe(var_v)))
    # Pairs with < 2 co-rated items carry no signal.
    return jnp.where(n_co >= 2, sim, 0.0)


def adjusted_cosine_matrix(R: jax.Array) -> jax.Array:
    """Item-based adjusted cosine: centre each *user's* ratings by their mean
    before the item-item cosine (Sarwar et al. 2001).  Expects R as
    (items, users): centring runs along axis 0 of the transpose layout."""
    Rf = R.astype(jnp.float32)
    B = (Rf != 0)
    user_sum = jnp.sum(Rf, axis=0)
    user_cnt = _safe(jnp.sum(B, axis=0).astype(jnp.float32))
    centred = jnp.where(B, Rf - (user_sum / user_cnt)[None, :], 0.0)
    return cosine_matrix(centred)


MEASURES = {
    "cosine": cosine_matrix,
    "pearson": pearson_matrix,
    "adjusted_cosine": adjusted_cosine_matrix,
}


def similarity_matrix(R: jax.Array, measure: str = "cosine") -> jax.Array:
    try:
        fn = MEASURES[measure]
    except KeyError:
        raise ValueError(f"unknown similarity measure {measure!r}; "
                         f"have {sorted(MEASURES)}")
    return fn(R)

"""Similarity measures vs direct NumPy references + invariants."""
from __future__ import annotations

import numpy as np
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (cosine_matrix, cosine_vs_all, pearson_matrix,
                        adjusted_cosine_matrix, row_norms, sort_rows)
from tests.conftest import make_ratings


def test_cosine_matches_numpy(rng):
    R = make_ratings(rng)
    S = np.asarray(cosine_matrix(jnp.asarray(R)))
    norms = np.linalg.norm(R, axis=1)
    ref = (R / norms[:, None]) @ (R / norms[:, None]).T
    np.testing.assert_allclose(S, ref, atol=1e-5)


def test_cosine_vs_all_consistent_with_matrix(rng):
    R = make_ratings(rng)
    S = np.asarray(cosine_matrix(jnp.asarray(R)))
    sims = np.asarray(cosine_vs_all(jnp.asarray(R),
                                    row_norms(jnp.asarray(R)),
                                    jnp.asarray(R[11])))
    np.testing.assert_allclose(sims, S[11], atol=1e-5)


def test_pearson_exact_co_support(rng):
    """Matmul-form Pearson == per-pair loop over co-rated items."""
    R = make_ratings(rng, n=25, m=18, density=0.5)
    S = np.asarray(pearson_matrix(jnp.asarray(R)))
    for u in range(0, 25, 7):
        for v in range(0, 25, 5):
            co = (R[u] != 0) & (R[v] != 0)
            if co.sum() < 2:
                assert S[u, v] == 0.0
                continue
            a, b = R[u][co].astype(np.float64), R[v][co].astype(np.float64)
            va = ((a - a.mean()) ** 2).sum()
            vb = ((b - b.mean()) ** 2).sum()
            if va < 1e-9 or vb < 1e-9:
                continue                     # degenerate: clamped in impl
            ref = ((a - a.mean()) * (b - b.mean())).sum() / np.sqrt(va * vb)
            np.testing.assert_allclose(S[u, v], ref, atol=1e-4)


def test_adjusted_cosine_centres_by_user(rng):
    R = make_ratings(rng, n=20, m=12, density=0.6)   # items x users layout
    S = np.asarray(adjusted_cosine_matrix(jnp.asarray(R)))
    assert S.shape == (20, 20)
    np.testing.assert_allclose(np.diag(S), 1.0, atol=1e-5)
    np.testing.assert_allclose(S, S.T, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_property_similarity_invariants(seed):
    rng = np.random.default_rng(seed)
    R = make_ratings(rng, n=30, m=12)
    S = np.asarray(cosine_matrix(jnp.asarray(R)))
    assert np.all(S <= 1.0 + 1e-5) and np.all(S >= -1.0 - 1e-5)
    np.testing.assert_allclose(np.diag(S), 1.0, atol=1e-5)
    np.testing.assert_allclose(S, S.T, atol=1e-6)
    # twins => identical similarity rows (Relationship 1)
    R2 = R.copy()
    R2[4] = R2[9]
    S2 = np.asarray(cosine_matrix(jnp.asarray(R2)))
    np.testing.assert_allclose(S2[4], S2[9], atol=1e-6)


def test_sorted_lists_ascending(rng):
    R = make_ratings(rng)
    S = cosine_matrix(jnp.asarray(R))
    vals, idx = sort_rows(S)
    v = np.asarray(vals)
    assert np.all(np.diff(v, axis=1) >= -1e-7)
    i = np.asarray(idx)
    for row in i[:5]:
        assert len(np.unique(row)) == len(row)

"""Properties and behaviour of the paper's core algorithm."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core import (build_state, make_probes, onboard_batch,
                        onboard_batch_traditional, set0_cap,
                        twinsearch_find)
from repro.core.reference import (build_sorted_lists_np, cosine_vs_all_np,
                                  twinsearch_np)
from tests.conftest import make_ratings


def _state(R, k):
    return build_state(jnp.asarray(R), capacity_extra=k)


class TestTwinFound:
    def test_planted_twin_is_found(self, rng):
        R = make_ratings(rng)
        n = R.shape[0]
        state = _state(R, 1)
        res = twinsearch_find(state, jnp.asarray(R[7]),
                              jnp.arange(6, dtype=jnp.int32),
                              s_max=set0_cap(n), n_base=n, k_cap=0)
        assert bool(res.found)
        # the verified twin's ratings are exactly the probe row
        assert np.array_equal(np.asarray(state.ratings[res.twin_idx]), R[7])

    def test_no_false_twin(self, rng):
        R = make_ratings(rng)
        n = R.shape[0]
        r0 = R[3].copy()
        r0[0] = 1.0 if r0[0] != 1.0 else 2.0            # perturb: no twin
        # ensure uniqueness
        assert not (R == r0).all(axis=1).any()
        state = _state(R, 1)
        res = twinsearch_find(state, jnp.asarray(r0),
                              jnp.arange(6, dtype=jnp.int32),
                              s_max=set0_cap(n), n_base=n, k_cap=0)
        assert not bool(res.found)

    def test_matches_numpy_oracle(self, rng):
        R = make_ratings(rng, n=150, m=50)
        n = R.shape[0]
        sv, si = build_sorted_lists_np(R)
        state = _state(R, 1)
        probes = np.asarray([3, 50, 77, 140])
        for src in (0, 42, 99):
            r0 = R[src]
            found_np, twin_np, set0 = twinsearch_np(R, sv, si, r0, probes)
            res = twinsearch_find(state, jnp.asarray(r0),
                                  jnp.asarray(probes, jnp.int32),
                                  s_max=set0_cap(n), n_base=n, k_cap=0)
            assert bool(res.found) == found_np
            # both twins must verify exactly (indices may differ on ties)
            assert np.array_equal(np.asarray(
                state.ratings[res.twin_idx]), r0)
            assert int(res.n_candidates) == len(set0)

    def test_overflow_flag(self, rng):
        R = make_ratings(rng)
        n = R.shape[0]
        state = _state(R, 1)
        res = twinsearch_find(state, jnp.asarray(R[7]),
                              jnp.arange(4, dtype=jnp.int32), s_max=n,
                              n_base=n, k_cap=0)
        assert not bool(res.overflowed)
        # s_max=0-ish cap forces overflow reporting when candidates exist
        res2 = twinsearch_find(state, jnp.asarray(R[7]),
                               jnp.arange(4, dtype=jnp.int32), s_max=1,
                               n_base=n, k_cap=0)
        assert int(res2.n_candidates) >= 1


class TestOnboardEquivalence:
    """The paper's guarantee: the copied list is the traditional list."""

    @pytest.mark.parametrize("burst", ["twins", "mixed", "all_fresh"])
    def test_burst_matches_traditional(self, rng, burst):
        R = make_ratings(rng, n=100, m=30)
        n = R.shape[0]
        if burst == "twins":
            R_new = np.tile(R[17], (6, 1))
        elif burst == "mixed":
            fresh = make_ratings(rng, n=1, m=30)[0]
            R_new = np.stack([R[17], fresh, R[17], fresh, fresh, R[3]])
        else:
            R_new = make_ratings(np.random.default_rng(9), n=6, m=30)
        k = R_new.shape[0]
        st_tw, stats = onboard_batch(_state(R, k), jnp.asarray(R_new),
                                     make_probes(jax.random.PRNGKey(0), k,
                                                 6, n))
        st_tr = onboard_batch_traditional(_state(R, k), jnp.asarray(R_new))
        for j in range(k):
            v1 = np.asarray(st_tw.sim_vals[n + j])
            v2 = np.asarray(st_tr.sim_vals[n + j])
            np.testing.assert_allclose(v1, v2, atol=2e-5)
            # idx consistency: sorted values must match the sims they index
            idx = np.asarray(st_tw.sim_idx[n + j])
            assert len(np.unique(idx)) == len(idx)

    def test_twin_hits_expected(self, rng):
        """k identical users: user 1 falls back, users 2..k hit."""
        R = make_ratings(rng, n=80, m=25)
        n = R.shape[0]
        fresh = make_ratings(np.random.default_rng(5), n=1, m=25)[0]
        assert not (R == fresh).all(axis=1).any()
        k = 5
        R_new = np.tile(fresh, (k, 1))
        _, stats = onboard_batch(_state(R, k), jnp.asarray(R_new),
                                 make_probes(jax.random.PRNGKey(1), k, 6, n))
        found = np.asarray(stats.found)
        assert not found[0]                  # no twin exists yet
        assert found[1:].all()               # later users twin user n+0


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(10, 60),
       st.integers(8, 30), st.integers(2, 8))
def test_property_planted_twin_always_found(seed, n, m, c):
    """For ANY rating matrix and ANY probe set, a planted exact twin is
    found and its copied list equals the traditional build."""
    rng = np.random.default_rng(seed)
    R = make_ratings(rng, n=n, m=m)
    src = int(rng.integers(0, n))
    state = build_state(jnp.asarray(R), capacity_extra=1)
    probes = jnp.asarray(rng.integers(0, n, c), jnp.int32)
    res = twinsearch_find(state, jnp.asarray(R[src]), probes,
                          s_max=max(8, n), n_base=n, k_cap=0)
    assert bool(res.found)
    assert np.array_equal(np.asarray(state.ratings[res.twin_idx]), R[src])


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_property_set0_contains_all_twins(seed):
    """|Set_0| >= number of exact twins (candidate generation is sound)."""
    rng = np.random.default_rng(seed)
    R = make_ratings(rng, n=50, m=15, density=0.5)
    R[10] = R[20]
    R[30] = R[20]                             # 3-way twin group
    state = build_state(jnp.asarray(R), capacity_extra=1)
    probes = jnp.asarray(rng.integers(0, 50, 5), jnp.int32)
    res = twinsearch_find(state, jnp.asarray(R[20]), probes, s_max=50,
                          n_base=50, k_cap=0)
    n_twins = int((R == R[20]).all(axis=1).sum())
    assert int(res.n_candidates) >= n_twins
    assert bool(res.found)

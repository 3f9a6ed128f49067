"""Incremental similarity maintenance for *existing* users.

This is the related-work path (Papagelis et al., ISMIS'05) the paper
contrasts with: when an existing user adds/changes a rating, the cached
dot-products let the affected similarity row refresh in O(n + n log n)
instead of an O(n m) rebuild.  TwinSearch covers the complementary case
(new users with duplicate rows); a production system runs both.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.similarity import HIGHEST
from repro.core.types import CFState, SENTINEL, active_mask


class SimCache(NamedTuple):
    dots: jax.Array      # (N, N) cached R @ R.T
    sq: jax.Array        # (N,)   cached ||r_u||^2


def init_cache(ratings: jax.Array) -> SimCache:
    Rf = ratings.astype(jnp.float32)
    return SimCache(dots=jnp.matmul(Rf, Rf.T, precision=HIGHEST),
                    sq=jnp.sum(jnp.square(Rf), axis=1))


def add_rating(state: CFState, cache: SimCache, user: jax.Array,
               item: jax.Array, rating: jax.Array
               ) -> tuple[CFState, SimCache]:
    """User ``user`` sets item ``item`` to ``rating`` (0 removes).

    Incremental identities (e = r_new − r_old on coordinate ``item``):
      dots[u, v] += e · R[v, item]      ∀v        — O(n)
      sq[u]      += r_new² − r_old²
    then only row u of the sorted lists re-sorts — O(n log n).
    """
    Rf = state.ratings
    r_old = Rf[user, item]
    e = rating.astype(jnp.float32) - r_old.astype(jnp.float32)

    col = Rf[:, item].astype(jnp.float32)
    new_dots_row = cache.dots[user] + e * col
    # The u-u self dot also gains e·r_old from the column term; fix exactly:
    self_dot = cache.sq[user] + 2 * r_old * e + e * e
    new_dots_row = new_dots_row.at[user].set(self_dot)
    dots = cache.dots.at[user].set(new_dots_row).at[:, user].set(new_dots_row)
    sq = cache.sq.at[user].set(self_dot)

    ratings = Rf.at[user, item].set(rating.astype(Rf.dtype))
    norms = state.norms.at[user].set(jnp.sqrt(self_dot))

    denom = jnp.maximum(jnp.sqrt(self_dot) * jnp.maximum(
        jnp.sqrt(sq), 1e-12), 1e-12)
    sims = new_dots_row / denom
    sims = jnp.where(active_mask(state), sims, SENTINEL)
    idx = jnp.argsort(sims).astype(jnp.int32)
    vals = jnp.take_along_axis(sims, idx, axis=-1)

    new_state = CFState(
        ratings=ratings,
        norms=norms,
        sim_vals=state.sim_vals.at[user].set(vals),
        sim_idx=state.sim_idx.at[user].set(idx),
        n_active=state.n_active,
    )
    return new_state, SimCache(dots=dots, sq=sq)

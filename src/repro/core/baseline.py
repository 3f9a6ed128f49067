"""Traditional new-user similarity-list construction (the paper's baseline).

For a new user u0: compute sim(u0, x) for every active user x — O(n m) — and
sort — O(n log n).  This is the path TwinSearch displaces; it is also
TwinSearch's fallback when no twin verifies.

The batched burst (``onboard_batch_traditional``) fuses the k per-user
matvecs into one (k, m) × (m, N) ``similarity_pallas`` matmul: the ratings
arena streams through the MXU once instead of k times, and the per-step
active mask (user t sees only rows < n_base + t) is applied to the result
block before the vectorised per-row sort.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import CFState, SENTINEL, active_mask
from repro.core.similarity import cosine_vs_all


def build_list(state: CFState, r0: jax.Array
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Similarity list of a new user vs the whole active system.

    Returns (vals_sorted_asc, idx_sorted, sims_unsorted) padded to capacity
    with SENTINEL for inactive slots.  ``sims_unsorted`` feeds the optional
    list-maintenance op (inserting u0 into existing users' lists)."""
    sims = cosine_vs_all(state.ratings, state.norms, r0)
    sims = jnp.where(active_mask(state), sims, SENTINEL)
    idx = jnp.argsort(sims).astype(jnp.int32)
    vals = jnp.take_along_axis(sims, idx, axis=-1)
    return vals, idx, sims


def append_user(state: CFState, r0: jax.Array, vals: jax.Array,
                idx: jax.Array) -> CFState:
    """Write the new user into the next capacity slot (static shapes)."""
    slot = state.n_active
    r0f = r0.astype(state.ratings.dtype)
    return CFState(
        ratings=jax.lax.dynamic_update_index_in_dim(
            state.ratings, r0f, slot, axis=0),
        norms=state.norms.at[slot].set(jnp.linalg.norm(
            r0.astype(jnp.float32))),
        sim_vals=jax.lax.dynamic_update_index_in_dim(
            state.sim_vals, vals.astype(state.sim_vals.dtype), slot, axis=0),
        sim_idx=jax.lax.dynamic_update_index_in_dim(
            state.sim_idx, idx.astype(jnp.int32), slot, axis=0),
        n_active=state.n_active + 1,
    )


def onboard_traditional(state: CFState, r0: jax.Array) -> CFState:
    """One new user through the traditional path (compute-all + sort)."""
    vals, idx, _ = build_list(state, r0)
    return append_user(state, r0, vals, idx)


def onboard_batch_traditional(state: CFState, R_new: jax.Array, *,
                              fused: bool = True) -> CFState:
    """k new users via the traditional path — the paper's O(k n m).

    ``fused=True`` (default) computes every burst user's similarities in a
    single (k, m) × (m, N) Pallas matmul over the post-append ratings
    arena; ``fused=False`` keeps the sequential per-user scan (the
    reference the fused path is tested against).  Both produce user t's
    list over exactly the rows active at its append (earlier burst users
    included, later ones SENTINEL), matching the one-at-a-time flow.
    """
    if not fused:
        def step(st, r0):
            return onboard_traditional(st, r0), ()
        state, _ = jax.lax.scan(step, state, R_new)
        return state

    from repro.kernels.similarity.ops import cosine_similarity

    k, _ = R_new.shape
    N = state.capacity
    slot0 = state.n_active
    Rf = R_new.astype(state.ratings.dtype)
    ratings = jax.lax.dynamic_update_slice(state.ratings, Rf,
                                           (slot0, jnp.int32(0)))
    new_norms = jax.vmap(jnp.linalg.norm)(R_new.astype(jnp.float32))
    norms = jax.lax.dynamic_update_slice(state.norms, new_norms, (slot0,))

    # One (k, m) x (m, N) fused-epilogue matmul instead of k matvecs.
    S = cosine_similarity(R_new.astype(jnp.float32), ratings,
                          new_norms, norms)
    cols = jnp.arange(N, dtype=jnp.int32)[None, :]
    seen = slot0 + jnp.arange(k, dtype=jnp.int32)[:, None]
    S = jnp.where(cols < seen, S, SENTINEL)              # per-step active set
    idx = jnp.argsort(S, axis=1).astype(jnp.int32)
    vals = jnp.take_along_axis(S, idx, axis=1)

    return CFState(
        ratings=ratings,
        norms=norms,
        sim_vals=jax.lax.dynamic_update_slice(
            state.sim_vals, vals.astype(state.sim_vals.dtype),
            (slot0, jnp.int32(0))),
        sim_idx=jax.lax.dynamic_update_slice(state.sim_idx, idx,
                                             (slot0, jnp.int32(0))),
        n_active=state.n_active + k,
    )

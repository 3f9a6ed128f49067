"""Burst-batched sorted-list maintenance at MovieLens scale.

The traditional flow inserts each onboarded user into every stored list one
at a time: k sequential shift-gather passes over the (N, L) arena, k * O(N^2)
work and k kernel launches.  The batched path merges all k (value, index)
pairs per row in ONE fused pass — O(N * (N + k)) — and must produce
bit-identical arenas (asserted below, not just benchmarked).

CSV columns (see benchmarks/run.py): ``name`` is
``maintenance_{seq|batched}_k{k}``, ``us_per_call`` the median wall
microseconds of one jit-compiled, block-until-ready call, and ``derived``
carries ``speedup=<seq/batched>`` on the batched rows (plus the
``traditional_{scan|fused}_k{k}`` build-phase rows with the same layout).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import CSV, time_call
from repro.core import build_state, insert_batch_into_lists, insert_into_lists
from repro.core import baseline

N_USERS, N_ITEMS = 943, 1682            # MovieLens-100k
K_SWEEP = (1, 5, 10, 20, 30)


def _ratings(rng, n, m, density=0.06):
    R = (rng.integers(1, 6, (n, m)) * (rng.random((n, m)) < density)
         ).astype(np.float32)
    R[R.sum(axis=1) == 0, 0] = 3.0
    return R


def _seq_insert(state, new_users, sims_block):
    """k sequential ``insert_into_lists`` calls under one jit, with the
    per-step ``n_active`` the interleaved flow would see (so the gates —
    and therefore the output — match the batched call exactly)."""
    def step(st, inp):
        u, sims = inp
        st = insert_into_lists(st._replace(n_active=u + 1), u, sims)
        return st, None
    out, _ = jax.lax.scan(step, state, (new_users, sims_block))
    return out._replace(n_active=state.n_active)


def main(csv: CSV) -> None:
    rng = np.random.default_rng(0)
    k_max = max(K_SWEEP)
    R = _ratings(rng, N_USERS, N_ITEMS)
    R_new = _ratings(rng, k_max, N_ITEMS)
    state = build_state(jnp.asarray(R), capacity_extra=k_max)
    for t in range(k_max):
        vals, idx, _ = baseline.build_list(state, jnp.asarray(R_new[t]))
        state = baseline.append_user(state, jnp.asarray(R_new[t]), vals, idx)
    sims_full = jnp.asarray(np.stack([
        np.asarray(baseline.build_list(
            state._replace(n_active=jnp.int32(N_USERS + t)),
            jnp.asarray(R_new[t]))[2]) for t in range(k_max)]))

    seq = jax.jit(_seq_insert)
    bat = jax.jit(lambda st, u, s: insert_batch_into_lists(st, u, s))
    for k in K_SWEEP:
        users = N_USERS + jnp.arange(k, dtype=jnp.int32)
        sims = sims_full[:k]
        a = seq(state, users, sims)
        b = bat(state, users, sims)
        if not (np.array_equal(np.asarray(a.sim_vals), np.asarray(b.sim_vals))
                and np.array_equal(np.asarray(a.sim_idx),
                                   np.asarray(b.sim_idx))):
            raise AssertionError(f"batched insert not bit-exact at k={k}")
        t_seq = time_call(seq, state, users, sims)
        t_bat = time_call(bat, state, users, sims)
        csv.add(f"maintenance_seq_k{k}", t_seq)
        csv.add(f"maintenance_batched_k{k}", t_bat,
                f"speedup={t_seq / t_bat:.2f}")

    # traditional build phase: per-user scan vs one fused (k, m) matmul
    base = build_state(jnp.asarray(R), capacity_extra=k_max)
    for k in (5, 30):
        rows = jnp.asarray(R_new[:k])
        scan_fn = jax.jit(lambda st, rn: baseline.onboard_batch_traditional(
            st, rn, fused=False))
        fused_fn = jax.jit(lambda st, rn: baseline.onboard_batch_traditional(
            st, rn, fused=True))
        t_scan = time_call(scan_fn, base, rows)
        t_fused = time_call(fused_fn, base, rows)
        csv.add(f"traditional_scan_k{k}", t_scan)
        # on CPU the fused path pays Pallas interpret-mode emulation for
        # its one (k, m) x (m, N) kernel call; the ratio is only
        # hardware-meaningful on a TPU, where the kernel compiles
        csv.add(f"traditional_fused_k{k}", t_fused,
                f"speedup={t_scan / t_fused:.2f} (interpret-mode)")


if __name__ == "__main__":
    c = CSV()
    c.header()
    main(c)

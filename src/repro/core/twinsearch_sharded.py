"""Distributed TwinSearch under ``shard_map`` — the web-scale serving path.

GSPMD cannot partition dynamic row lookups (probe-list fetches, twin-row
copies) on the row-sharded (N, N) similarity store: it falls back to
"involuntary full rematerialization", replicating the whole arena
(measured 8TB/device temp at web scale — §Perf Cell C).  Here every
distributed access is explicit and intrinsic-cost:

  * probe rows / twin rows: masked local ``dynamic_slice`` + ``psum``
    (exactly one row of traffic per fetch);
  * candidate verification: **shard-local** — each shard gathers only its
    own candidate rows (a local HBM read) and contributes one bool per
    candidate; cross-device traffic for the paper's O(|Set_0|·m) term is
    ~s_max bits;
  * the traditional fallback: local matvec + one tiled ``all_gather``;
  * the burst accumulates in a replicated (k, N+k) write buffer; the base
    arena is never written (LSM-style, merged offline).

Per-user collective bytes ≈ (c+2)·N·4 — independent of m, ~3 orders below
the GSPMD formulation at the Douban scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.types import CFState, OnboardStats, SENTINEL


def _shard_id(axes: tuple[str, ...], sizes: dict[str, int]) -> jax.Array:
    sid = jnp.int32(0)
    for a in axes:
        sid = sid * sizes[a] + jax.lax.axis_index(a)
    return sid


def onboard_batch_sharded(state: CFState, R_new: jax.Array,
                          probe_idx: jax.Array, *, s_max: int,
                          axes: tuple[str, ...], mesh, tol: float = 1e-6,
                          unroll: bool = False, maintain: bool = False):
    """state arrays row-sharded P(axes, ...); returns (vals, idx, stats)
    for the k new users, lists over N_base + k entries (ascending).

    ``maintain=True`` appends a fourth element (base_vals, base_idx): the
    row-sharded (N_base, N_base + k) base lists with the whole burst
    merged in.  The k-way merge-insert is row-local — each shard merges
    only its own rows, reading its slice of the replicated write buffer —
    so batched maintenance adds **zero** collective traffic on top of the
    onboarding scan (vs k full shift-gather passes sequentially).
    """
    N_base = state.capacity
    k, m = R_new.shape
    N_tot = N_base + k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = 1
    for a in axes:
        n_shards *= sizes[a]
    rows_loc = N_base // n_shards
    s_loc = min(s_max, rows_loc)

    Rn_new = R_new.astype(jnp.float32)
    new_norms = jnp.sqrt(jnp.sum(jnp.square(Rn_new), axis=1))
    karange = jnp.arange(k, dtype=jnp.int32)

    def local(ratings, norms, sim_vals, sim_idx, R_new_, probes_):
        sid = _shard_id(axes, sizes)
        offset = sid * rows_loc

        def fetch(arr, g, width):
            """Replicated row ``g`` of a row-sharded (rows_loc, width)."""
            r = jnp.clip(g - offset, 0, rows_loc - 1)
            row = jax.lax.dynamic_slice(arr, (r, 0), (1, width))[0]
            mine = (g >= offset) & (g < offset + rows_loc)
            return jax.lax.psum(jnp.where(mine, row, 0), axes)

        def step(carry, inp):
            buf, j = carry
            r0, probes = inp
            r0f = r0.astype(jnp.float32)
            r0n = jnp.maximum(jnp.linalg.norm(r0f), 1e-12)

            # --- probe sims: dot on the owning shard, psum scalars -----
            def one_probe(p):
                r = jnp.clip(p - offset, 0, rows_loc - 1)
                row = jax.lax.dynamic_slice(ratings, (r, 0), (1, m))[0]
                nrm = jax.lax.dynamic_slice(norms, (r,), (1,))[0]
                mine = (p >= offset) & (p < offset + rows_loc)
                d = jnp.dot(row.astype(jnp.float32), r0f)
                d = d / (jnp.maximum(nrm, 1e-12) * r0n)
                return jnp.where(mine, d, 0.0)
            sims0 = jax.lax.psum(jax.vmap(one_probe)(probes), axes)  # (c,)

            # --- equal-range search + mask intersect (replicated) ------
            rows_v = jax.vmap(lambda p: fetch(sim_vals, p, N_base))(probes)
            rows_i = jax.vmap(lambda p: fetch(
                sim_idx.astype(jnp.float32), p, N_base))(probes).astype(
                jnp.int32)
            lo = jax.vmap(lambda row, s: jnp.searchsorted(
                row, s, side="left"))(rows_v, sims0 - tol)
            hi = jax.vmap(lambda row, s: jnp.searchsorted(
                row, s, side="right"))(rows_v, sims0 + tol)
            pos = jnp.arange(N_base, dtype=jnp.int32)[None, :]
            in_range = (pos >= lo[:, None]) & (pos < hi[:, None])
            c = probes.shape[0]
            umask = jnp.zeros((c, N_base), bool).at[
                jnp.arange(c)[:, None], rows_i].set(in_range)
            umask = umask.at[jnp.arange(c), probes].max(
                jnp.abs(sims0 - 1.0) <= tol)
            cand = jnp.all(umask, axis=0)                # (N_base,) repl.

            # --- shard-local verification ------------------------------
            mask_loc = jax.lax.dynamic_slice(cand, (offset,), (rows_loc,))
            n_cand = jax.lax.psum(jnp.sum(mask_loc, dtype=jnp.int32), axes)
            _, lidx = jax.lax.top_k(mask_loc.astype(jnp.float32), s_loc)
            lvalid = mask_loc[lidx]
            lrows = ratings[lidx]                        # local HBM gather
            leq = jnp.all(lrows == r0.astype(lrows.dtype)[None, :],
                          axis=1) & lvalid
            found_b_loc = jnp.any(leq)
            best_loc = jnp.where(found_b_loc,
                                 offset + lidx[jnp.argmax(leq)], -1)
            found_b = jax.lax.psum(found_b_loc.astype(jnp.int32), axes) > 0
            twin_b = jax.lax.pmax(best_loc, axes)
            overflow = jax.lax.psum(
                (jnp.sum(mask_loc, dtype=jnp.int32) > s_loc).astype(
                    jnp.int32), axes) > 0

            # --- burst-internal twins (replicated, no state reads) ------
            live = karange < j
            eq_new = jnp.all(R_new_ == r0[None, :], axis=1) & live
            found_n = jnp.any(eq_new)
            twin_n = jnp.argmax(eq_new).astype(jnp.int32)

            bsims = jnp.einsum("km,m->k", Rn_new, r0f) / (
                jnp.maximum(new_norms, 1e-12) * r0n)
            bsims = jnp.where(live, bsims, SENTINEL)

            # --- row construction: copy / copy-new / fallback ----------
            def fallback(_):
                d_loc = jnp.einsum("nm,m->n", ratings.astype(jnp.float32),
                                   r0f)
                s_loc_v = d_loc / (jnp.maximum(norms, 1e-12) * r0n)
                return jax.lax.all_gather(s_loc_v, axes, axis=0,
                                          tiled=True)

            def copy_base(_):
                tvals = fetch(sim_vals, twin_b, N_base)
                tidx = fetch(sim_idx.astype(jnp.float32), twin_b,
                             N_base).astype(jnp.int32)
                u = jnp.full((N_base,), SENTINEL, jnp.float32)
                return u.at[tidx].set(tvals)

            def copy_new(_):
                return buf[twin_n, :N_base]

            branch = jnp.where(found_b, 1, jnp.where(found_n, 2, 0))
            base_row = jax.lax.switch(branch,
                                      [fallback, copy_base, copy_new],
                                      None)
            row = jnp.concatenate([base_row, bsims])
            buf = jax.lax.dynamic_update_index_in_dim(buf, row, j, axis=0)
            found = found_b | found_n
            twin = jnp.where(found_b, twin_b, N_base + twin_n)
            return (buf, j + 1), (found, twin, n_cand, overflow)

        buf0 = jnp.full((k, N_tot), SENTINEL, jnp.float32)
        (buf, _), outs = jax.lax.scan(step, (buf0, jnp.int32(0)),
                                      (R_new_, probes_),
                                      unroll=k if unroll else 1)
        idx = jnp.argsort(buf, axis=1).astype(jnp.int32)
        vals = jnp.take_along_axis(buf, idx, axis=1)
        if not maintain:
            return vals, idx, outs
        # Shard-local batched maintenance: merge the burst into this
        # shard's (rows_loc, N_base) lists, fed by the local column slice
        # of the replicated write buffer.  No collectives.
        sid = _shard_id(axes, sizes)
        sims_loc = jax.lax.dynamic_slice(buf, (0, sid * rows_loc),
                                         (k, rows_loc))
        from repro.core.maintenance import merge_new_users_into_base
        m_vals, m_idx = merge_new_users_into_base(
            sim_vals, sim_idx, sims_loc,
            N_base + jnp.arange(k, dtype=jnp.int32), use_pallas=False)
        return vals, idx, outs, (m_vals, m_idx)

    rows = P(axes, None)
    out_specs = (P(None, None), P(None, None),
                 (P(None), P(None), P(None), P(None)))
    if maintain:
        out_specs = out_specs + ((rows, rows),)
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(rows, P(axes), rows, rows, P(None, None), P(None, None)),
        out_specs=out_specs,
        check_vma=False,
    )(state.ratings, state.norms, state.sim_vals, state.sim_idx, R_new,
      probe_idx)
    vals, idx, (found, twin, ncand, ovf) = out[:3]
    stats = OnboardStats(found=found, twin_idx=twin, n_candidates=ncand,
                         overflowed=ovf)
    if maintain:
        return vals, idx, stats, out[3]
    return vals, idx, stats


def onboard_batch_resilient(state: CFState, R_new: jax.Array,
                            probe_idx: jax.Array, *, s_max: int,
                            axes: tuple[str, ...], mesh,
                            replicas=None, retry=None, tol: float = 1e-6,
                            unroll: bool = False, maintain: bool = False):
    """``onboard_batch_sharded`` behind the serving resilience layer.

    Pre-flight, the replicated arena (``distributed/replication.py``)
    sweeps replica health and heals any poisoned primary rows from
    surviving replicas — pure data movement, so a dead shard's garbage
    never feeds the scan.  The shard_map launch itself runs under the
    serving ``RetryPolicy`` (transient executor faults retry with
    backoff).  Returns ``(state, result)``: ``state`` is the (possibly
    healed) arena the scan actually ran on.

    Raises ``RuntimeError`` if a poisoned row has no surviving replica —
    at that point only a snapshot rollback (the serving layer's job) can
    help, and running the scan over garbage would waste the collective
    traffic.
    """
    from repro.serving import guard as _guard       # no import cycle: lazy

    if replicas is not None:
        replicas.sweep()
        fixed, rows = replicas.repair(state)
        if fixed is None:
            raise RuntimeError(
                f"{rows.size} arena rows unrecoverable (all replicas of "
                f"their shard down); roll back to a snapshot")
        state = fixed

    def run():
        out = onboard_batch_sharded(state, R_new, probe_idx, s_max=s_max,
                                    axes=axes, mesh=mesh, tol=tol,
                                    unroll=unroll, maintain=maintain)
        jax.block_until_ready(out)
        return out

    result, _retries = _guard.call_with_retry(
        run, retry or _guard.RetryPolicy())
    return state, result

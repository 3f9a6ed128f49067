"""Arena rotation: grow a full ``CFState`` into a larger one without
recomputing a single similarity.

The serving arena is fixed-capacity (N = n_base + k_cap) so every mutating
op stays jit-able with static shapes.  When a traffic burst fills all
``k_cap`` onboarding slots the old behaviour was to raise — exactly at the
moment the paper's fast path is paying off.  Rotation instead *compacts*
the write region into a new, larger base arena:

  * the k onboarded users' own lists already hold sim(u_t, x) for every
    base row x — their unsorted rows are recovered by scattering each
    sorted list back through its permutation (pure data movement);
  * every base row receives all k new entries in ONE fused k-way
    merge-insert (PR 1's ``merge_new_users_into_base``) fed by that
    recovered block — O(N·(N + k)) total instead of k·O(N²), and zero
    similarity recompute;
  * the burst block's mutual similarities are completed by symmetry
    (sim(u_t, u_s) is stored in whichever of the two rows was appended
    later) and each new row gains its self-entry, making the k users
    first-class base citizens;
  * ``extra`` fresh all-sentinel slots are appended as the new write
    region.

Everything is a rearrangement of values already in the arena, so the
rotated lists are bit-exact to what the sequential insert flow would have
produced (asserted against a numpy re-sort oracle in
``tests/test_resilience.py``) and match a fresh traditional build to float
tolerance (stored sims came from ``cosine_vs_all``; a fresh build's
``cosine_matrix`` rounds differently).

Rows refreshed mid-epoch by ``add_rating`` re-sort over the *current*
active set and may therefore already contain write-region entries; rotation
gates those out before the merge so no row ends up with duplicates.

Two execution modes share the same per-row ops (so they are bit-exact by
construction):

  * ``rotate_arena`` — the one-shot synchronous rotation: compact the
    whole write region ``[n_base, n_active)`` now;
  * ``RotationPlan`` — the chunked, resumable rotation: freeze the burst
    boundary at plan start, merge base rows in bounded slices
    (``step(state, budget_rows)``) into accumulators kept on the device
    while new onboards keep landing past the frozen boundary, then
    ``finalize(state)`` assembles the new arena in one program: the
    atomic swap.  Rows onboarded mid-plan are *carried* into the new write
    region unchanged (onboarding only ever writes the new user's own
    row); base rows refreshed mid-plan by ``add_rating`` are re-merged at
    finalize from the live state, and a refresh of a frozen burst row
    invalidates the recovered block and restarts the (idempotent)
    precompute.  ``finalize`` is therefore bit-identical to the one-shot
    ``rotate_arena_frozen`` applied to the live state at swap time —
    which is what crash recovery replays from the WAL's ``rotate_commit``
    record.
"""
from __future__ import annotations

import math
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import CFState, SENTINEL, SENTINEL_GATE
from repro.core.maintenance import merge_new_users_into_base


def unsorted_rows(sim_vals: jax.Array, sim_idx: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """(k, N) unsorted similarity rows recovered from sorted lists.

    Each row's ``sim_idx`` is a permutation of 0..N-1 (argsort output), so
    scattering the sorted values back through it reconstructs the original
    column order; sentinel entries land on the columns that were inactive
    at the row's build."""
    N = sim_vals.shape[1]

    def one(v: jax.Array, i: jax.Array) -> jax.Array:
        return jnp.full((N,), SENTINEL, v.dtype).at[i].set(v)

    return jax.vmap(one)(sim_vals[rows], sim_idx[rows])


def _fit_width(vals: jax.Array, idx: jax.Array,
               width: int) -> tuple[jax.Array, jax.Array]:
    """Pad (head sentinels) or trim (head entries, sentinels by
    construction) ascending lists to ``width`` columns."""
    rows, cur = vals.shape
    if cur == width:
        return vals, idx
    if cur < width:
        pad_v = jnp.full((rows, width - cur), SENTINEL, vals.dtype)
        pad_i = jnp.full((rows, width - cur), -1, idx.dtype)
        return (jnp.concatenate([pad_v, vals], axis=1),
                jnp.concatenate([pad_i, idx], axis=1))
    return vals[:, cur - width:], idx[:, cur - width:]


# ---------------------------------------------------------------------------
# Shared per-row ops — every rotation mode goes through these, so chunked
# and one-shot results are bit-identical (pure data movement, row-local).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_base", "use_pallas"))
def _merge_base_rows(sim_vals: jax.Array, sim_idx: jax.Array, U: jax.Array,
                     rows: jax.Array, buf_ids: jax.Array, *, n_base: int,
                     use_pallas: bool | None) -> tuple[jax.Array, jax.Array]:
    """Gate + stable re-sort + k-way merge for the base rows ``rows``.

    ``rows`` is (b,) int32 ids in [0, n_base); duplicates (chunk padding)
    compute redundantly and are discarded by the caller.  Returns the
    merged ascending (b, L + k) lists.  Row-local: processing rows in any
    grouping yields bitwise-identical rows."""
    gv_raw = sim_vals[rows]
    gi_raw = sim_idx[rows]
    # Gate out any write-region entries (rows refreshed by add_rating
    # already carry them), stable re-sort so the gated lists are ascending
    # again, then merge the whole burst in one pass.  The ids ride the
    # sort as its payload: permuting the lists by per-element gathers
    # (argsort + take_along_axis) takes a TPU far longer than the sort.
    gate = gi_raw < n_base
    gv = jnp.where(gate, gv_raw, SENTINEL)
    gi = jnp.where(gate, gi_raw, -1)
    gv, gi = lax.sort((gv, gi), dimension=1, is_stable=True, num_keys=1)
    mv, mi = merge_new_users_into_base(gv, gi, U[:, rows], buf_ids,
                                       use_pallas=use_pallas)
    return mv, mi.astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_base",))
def _freeze(sim_vals: jax.Array, sim_idx: jax.Array, buf_ids: jax.Array, *,
            n_base: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """A plan's start, as one program: the burst's recovered rows ``U``
    and zeroed (n_base, L + k) accumulators for the merged base rows."""
    width = sim_vals.shape[1] + buf_ids.shape[0]
    return (unsorted_rows(sim_vals, sim_idx, buf_ids),
            jnp.zeros((n_base, width), jnp.float32),
            jnp.zeros((n_base, width), jnp.int32))


@partial(jax.jit, donate_argnums=(0, 1))
def _scatter_rows(acc_v: jax.Array, acc_i: jax.Array, rows: jax.Array,
                  mv: jax.Array, mi: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Merged rows written into the accumulators (donated) at ``rows``;
    a repeated row carries the same merged values each time."""
    return acc_v.at[rows].set(mv.astype(acc_v.dtype)), acc_i.at[rows].set(mi)


def _burst_rows(U: jax.Array, *, n_base: int, n_frozen: int,
                n_new: int) -> tuple[jax.Array, jax.Array]:
    """Full-width sorted lists for the compacted burst rows.

    Base entries come straight from the recovered block; burst-internal
    entries complete by symmetry (row u_t holds sim(u_t, u_s) only for
    s < t — the transpose holds the rest); the self-entry a fresh build
    would carry is exactly 1."""
    k = n_frozen - n_base
    C = U[:, n_base:n_frozen]                            # (k, k)
    C = jnp.where(C > SENTINEL_GATE, C, jnp.swapaxes(C, 0, 1))
    C = C.at[jnp.arange(k), jnp.arange(k)].set(1.0)
    W = jnp.full((k, n_new), SENTINEL, jnp.float32)
    W = W.at[:, :n_base].set(U[:, :n_base].astype(jnp.float32))
    W = W.at[:, n_base:n_frozen].set(C.astype(jnp.float32))
    col = lax.broadcasted_iota(jnp.int32, W.shape, 1)
    return lax.sort((W, col), dimension=1, is_stable=True, num_keys=1)


def _assemble(state: CFState, mv: jax.Array | None, mi: jax.Array | None,
              U: jax.Array | None, *, n_base: int, n_frozen: int,
              n_act: int, extra: int) -> CFState:
    """The rotated arena from the live ``state`` and the merged base rows
    ``(mv, mi)`` (None when the burst is empty): the base rows re-fit to
    the new width, the burst rows built from ``U``, the rows onboarded
    after the boundary froze carried, and a fresh write region of
    ``extra`` slots.  Shared by both modes: run op by op in
    ``rotate_arena_frozen``, as one program in ``RotationPlan.finalize``."""
    n_new = n_act + extra
    m = state.n_items
    grow = n_new - n_act

    ratings = jnp.concatenate([
        state.ratings[:n_act],
        jnp.zeros((grow, m), state.ratings.dtype)], axis=0)
    norms = jnp.concatenate([
        state.norms[:n_act], jnp.zeros((grow,), state.norms.dtype)])

    if mv is None:                           # pure growth, nothing to merge
        base_v, base_i = _fit_width(state.sim_vals[:n_frozen],
                                    state.sim_idx[:n_frozen], n_new)
    else:
        mv, mi = _fit_width(mv, mi, n_new)
        bv, bi = _burst_rows(U, n_base=n_base, n_frozen=n_frozen,
                             n_new=n_new)
        base_v = jnp.concatenate([mv.astype(jnp.float32), bv], axis=0)
        base_i = jnp.concatenate([mi, bi], axis=0)

    blocks_v, blocks_i = [base_v], [base_i]
    if n_act > n_frozen:                     # carried mid-plan onboards
        cv, ci = _fit_width(state.sim_vals[n_frozen:n_act],
                            state.sim_idx[n_frozen:n_act], n_new)
        blocks_v.append(cv.astype(jnp.float32))
        blocks_i.append(ci)

    # Fresh write region: all-sentinel rows with identity permutations
    # (what ``build_state`` gives inactive slots).
    empty_v = jnp.full((grow, n_new), SENTINEL, jnp.float32)
    empty_i = jnp.broadcast_to(jnp.arange(n_new, dtype=jnp.int32),
                               (grow, n_new))
    return CFState(
        ratings=ratings,
        norms=norms,
        sim_vals=jnp.concatenate(blocks_v + [empty_v], axis=0),
        sim_idx=jnp.concatenate(blocks_i + [empty_i], axis=0),
        n_active=jnp.asarray(n_act, jnp.int32),
    )


_assemble_jit = jax.jit(
    _assemble, static_argnames=("n_base", "n_frozen", "n_act", "extra"))


def rotate_arena_frozen(state: CFState, *, n_base: int, n_frozen: int,
                        extra: int,
                        use_pallas: bool | None = None) -> CFState:
    """Compact the frozen burst ``[n_base, n_frozen)`` into a new base
    arena of capacity ``n_active + extra``; rows ``[n_frozen, n_active)``
    (onboarded after the boundary froze) are *carried* into the new write
    region with their lists re-fit to the new width — valid because
    onboarding only ever writes the new user's own row, so a carried
    row's list is exactly what onboarding into the new arena would have
    produced.  ``n_frozen == n_active`` reproduces the classic full
    rotation.  This is also the deterministic replay of a WAL
    ``rotate_commit`` record."""
    mv = mi = U = None
    if n_frozen > n_base:
        buf = jnp.arange(n_base, n_frozen, dtype=jnp.int32)
        U = unsorted_rows(state.sim_vals, state.sim_idx, buf)    # (k, N)
        mv, mi = _merge_base_rows(state.sim_vals, state.sim_idx, U,
                                  jnp.arange(n_base, dtype=jnp.int32), buf,
                                  n_base=n_base, use_pallas=use_pallas)
    return _assemble(state, mv, mi, U, n_base=n_base, n_frozen=n_frozen,
                     n_act=int(state.n_active), extra=extra)


def rotate_arena(state: CFState, *, n_base: int, extra: int,
                 headroom: float = 1.0,
                 use_pallas: bool | None = None) -> CFState:
    """Compact the write region [n_base, n_active) into a new base arena of
    capacity ``n_active + extra``.  Rotation is rare (once per k_cap
    onboards) and runs un-jitted at the top level; the merge underneath is
    the jitted ``merge_insert`` op.

    ``headroom`` is the rotation *hysteresis* knob: the fresh write region
    is at least ``headroom`` times the burst just absorbed, so a sustained
    flood that fills ``extra`` slots immediately gets a proportionally
    larger buffer next time instead of re-triggering a synchronous rotation
    after the same number of onboards.  ``headroom=1.0`` (the default)
    reproduces the fixed-size behaviour."""
    n_act = int(state.n_active)
    k = n_act - n_base
    extra = max(int(extra), int(math.ceil(float(headroom) * k)))
    return rotate_arena_frozen(state, n_base=n_base, n_frozen=n_act,
                               extra=extra, use_pallas=use_pallas)


class RotationPlan:
    """Chunked, resumable arena rotation with a frozen burst boundary.

    Created when the server decides to rotate *ahead* of exhaustion; the
    expensive part — gating + merging every base row — runs in bounded
    slices (``step``) interleaved with live traffic, each merge's rows
    scattered into accumulators kept on the device, and the cheap
    remainder (burst-row construction, carried rows, concatenation) runs
    once at ``finalize``, as one program.  The plan is pure precompute:
    it never mutates the state it reads, a crash mid-plan loses nothing
    (nothing is logged until the swap commits), and its output is
    bit-identical to ``rotate_arena_frozen(live_state, ...)`` at swap
    time.

    Live mutations are reconciled through ``note_write``:

      * a base row refreshed by ``add_rating`` is marked dirty and
        re-merged from the live state before the swap;
      * a *frozen burst* row refreshed invalidates the recovered U block —
        the precompute restarts from the live state (same boundary);
      * rows at or past ``n_frozen`` (mid-plan onboards) need nothing —
        ``finalize`` carries them straight from the live state.
    """

    def __init__(self, state: CFState, *, n_base: int, extra: int,
                 chunk_rows: int = 64, use_pallas: bool | None = None):
        self.n_base = int(n_base)
        self.n_frozen = int(state.n_active)
        self.k = self.n_frozen - self.n_base
        self.extra = int(extra)
        self.chunk = max(1, int(chunk_rows))
        self.use_pallas = use_pallas
        self.restarts = 0
        self.elapsed_ms = 0.0        # accumulated step+finalize time
        self._buf = jnp.arange(self.n_base, self.n_frozen, dtype=jnp.int32)
        self._U: jax.Array | None = None
        self._mv: jax.Array | None = None        # (n_base, L + k) on device
        self._mi: jax.Array | None = None
        self._cursor = 0
        self._dirty: set[int] = set()
        self._stale = self.k > 0     # U snapshot pending (or invalidated)

    # -- progress -----------------------------------------------------------

    @property
    def done(self) -> bool:
        """True when every base row is merged against the current U block
        and no dirty rows are pending — the swap would be O(burst+concat)."""
        if self.k == 0:
            return True
        return (not self._stale and self._cursor >= self.n_base
                and not self._dirty)

    @property
    def remaining_rows(self) -> int:
        if self.k == 0:
            return 0
        if self._stale:
            return self.n_base + len(self._dirty)
        return (self.n_base - self._cursor) + len(self._dirty)

    # -- live-mutation reconciliation ---------------------------------------

    def note_write(self, row: int) -> None:
        """Record that ``row``'s list/ratings were rewritten (add_rating)."""
        r = int(row)
        if r < self.n_base:
            if not self._stale:      # a pending refreeze re-reads everything
                self._dirty.add(r)
        elif r < self.n_frozen:
            # The recovered block holds this burst row's scattered list;
            # it is now stale.  Restart the precompute from the live state.
            if not self._stale:
                self._stale = True
                self.restarts += 1

    # -- bounded work -------------------------------------------------------

    def _refreeze(self, state: CFState) -> None:
        self._U, self._mv, self._mi = _freeze(
            state.sim_vals, state.sim_idx, self._buf, n_base=self.n_base)
        self._cursor = 0
        self._dirty.clear()
        self._stale = False

    def _run_rows(self, state: CFState, rows: np.ndarray) -> None:
        """One fixed-shape merge over ``rows`` (padded by repetition to
        the chunk width; pad lanes recompute a row already in the batch —
        row-local, so they write the same values), scattered into the
        accumulators."""
        n = rows.shape[0]
        if n < self.chunk:
            rows = np.concatenate(
                [rows, np.full(self.chunk - n, rows[-1], rows.dtype)])
        rows = jnp.asarray(rows, jnp.int32)
        mv, mi = _merge_base_rows(state.sim_vals, state.sim_idx, self._U,
                                  rows, self._buf, n_base=self.n_base,
                                  use_pallas=self.use_pallas)
        self._mv, self._mi = _scatter_rows(self._mv, self._mi, rows, mv, mi)

    def step(self, state: CFState, budget_rows: int) -> int:
        """Merge up to ``budget_rows`` base rows against the frozen block;
        returns the number of rows actually processed, once their merge
        has finished on the device.  Never mutates ``state``; safe to call
        at any point between server mutations."""
        if self.k == 0 or self.done:
            return 0
        t0 = time.perf_counter()
        if self._stale:
            self._refreeze(state)
        budget = max(1, int(budget_rows))
        processed = 0
        while processed < budget and self._cursor < self.n_base:
            hi = min(self._cursor + self.chunk, self.n_base)
            self._run_rows(state, np.arange(self._cursor, hi))
            processed += hi - self._cursor
            self._cursor = hi
        # Main sweep finished: re-merge rows dirtied since they were done.
        while processed < budget and self._cursor >= self.n_base \
                and self._dirty:
            batch = sorted(self._dirty)[:self.chunk]
            self._run_rows(state, np.asarray(batch))
            self._dirty.difference_update(batch)
            processed += len(batch)
        jax.block_until_ready((self._mv, self._mi))
        self.elapsed_ms += (time.perf_counter() - t0) * 1e3
        return processed

    # -- the atomic swap ----------------------------------------------------

    def finalize(self, state: CFState) -> CFState:
        """Produce the rotated state from the live ``state``: drain any
        remaining/dirty rows, then build the burst + carried blocks and
        assemble the new arena in one program.  Bit-identical to
        ``rotate_arena_frozen(state, n_base=.., n_frozen=.., extra=..)``."""
        while not self.done:                     # force-drain the tail
            self.step(state, self.n_base)
        t0 = time.perf_counter()
        out = _assemble_jit(state, self._mv, self._mi, self._U,
                            n_base=self.n_base, n_frozen=self.n_frozen,
                            n_act=int(state.n_active), extra=self.extra)
        self._mv = self._mi = None               # the swap consumed them
        self.elapsed_ms += (time.perf_counter() - t0) * 1e3
        return out

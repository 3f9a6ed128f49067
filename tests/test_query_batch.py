"""Batched query path: core batch endpoints, twin dedup, per-row guards.

Covers the PR-10 contracts:

  * ``top_k_neighbors`` with ``k > n_active - 1`` never leaks SENTINEL
    arena rows as neighbours (regression: padded/dead rows used to
    surface with sentinel weights and poison downstream gathers);
  * batched == scalar *bit-exact* on random states (``recommend_batch``
    / ``predict_batch`` are vmapped scalar paths, not approximations);
  * twin users (bitwise-identical dedup keys) provably share scores and
    are scored once;
  * a forced hash collision in the dedup probe never causes wrong
    sharing — the exact-verify step keeps distinct rows distinct;
  * a mixed valid/invalid batch quarantines the bad rows and serves the
    rest (no-raise contract extends to reads);
  * the shed rung degrades reads (smaller k) instead of refusing them.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SENTINEL_GATE, build_state, knn
from repro.serving import CFServer, LEVEL_SHED, ServerConfig
from repro.serving import dedup as dedup_mod
from repro.serving.dedup import dedup_rows, fan_out


def _ratings(rng, n, m, density=0.3):
    R = (rng.integers(1, 6, (n, m)) * (rng.random((n, m)) < density)
         ).astype(np.float32)
    R[R.sum(axis=1) == 0, 0] = 3.0
    return R


def _state(R, extra=8):
    return jax.block_until_ready(
        jax.jit(lambda r: build_state(r, capacity_extra=extra))(
            jnp.asarray(R)))


class TestTopKSmallActive:
    def test_k_exceeding_active_never_leaks_sentinel_rows(self):
        """k > n_active - 1: dead slots must gate to weight-SENTINEL and
        clamp to row 0, never expose padded arena rows."""
        rng = np.random.default_rng(0)
        n = 3
        R = _ratings(rng, n, 12)
        state = _state(R, extra=29)          # capacity 32 >> n_active 3
        for user in range(n):
            sims, nbrs = jax.device_get(
                knn.top_k_neighbors(state, jnp.int32(user), k=20))
            live = sims > SENTINEL_GATE
            assert live.sum() <= n - 1       # at most the other real users
            assert np.all(nbrs[live] < n)
            assert np.all(nbrs[live] != user)
            assert np.all(nbrs[~live] == 0)  # dead slots clamp to row 0

    def test_predictions_well_defined_with_oversized_k(self):
        rng = np.random.default_rng(1)
        R = _ratings(rng, 4, 10, density=0.9)
        state = _state(R, extra=28)
        p = float(knn.predict(state, jnp.int32(0), jnp.int32(3), k=25))
        assert np.isfinite(p)
        scores, items = jax.device_get(
            knn.recommend(state, jnp.int32(1), k_neighbors=25, n_rec=4))
        assert np.all(np.asarray(items) < 10)

    def test_matches_small_k_on_shared_prefix(self):
        """The first min(k, n_active-1) slots agree with a small-k call."""
        rng = np.random.default_rng(2)
        R = _ratings(rng, 5, 16)
        state = _state(R, extra=27)
        s_small, n_small = jax.device_get(
            knn.top_k_neighbors(state, jnp.int32(2), k=4))
        s_big, n_big = jax.device_get(
            knn.top_k_neighbors(state, jnp.int32(2), k=30))
        assert np.array_equal(s_small, s_big[:4])
        assert np.array_equal(n_small, n_big[:4])


class TestBatchedEqualsScalar:
    @pytest.mark.parametrize("seed,n,m", [(0, 20, 30), (1, 64, 17),
                                          (2, 7, 50)])
    def test_recommend_batch_bit_exact(self, seed, n, m):
        rng = np.random.default_rng(seed)
        state = _state(_ratings(rng, n, m))
        users = jnp.asarray(rng.integers(0, n, 13).astype(np.int32))
        bs, bi = jax.device_get(
            knn.recommend_batch(state, users, k_neighbors=5, n_rec=6))
        for r, u in enumerate(np.asarray(users)):
            ss, si = jax.device_get(
                knn.recommend(state, jnp.int32(int(u)), 5, 6))
            assert bs[r].tobytes() == np.asarray(ss).tobytes()
            assert np.array_equal(bi[r], np.asarray(si))

    @pytest.mark.parametrize("seed,n,m", [(3, 24, 40), (4, 9, 9)])
    def test_predict_batch_bit_exact(self, seed, n, m):
        rng = np.random.default_rng(seed)
        state = _state(_ratings(rng, n, m))
        users = rng.integers(0, n, 11).astype(np.int32)
        items = rng.integers(0, m, 11).astype(np.int32)
        bp = jax.device_get(knn.predict_batch(
            state, jnp.asarray(users), jnp.asarray(items), k=4))
        for r in range(11):
            sp = jax.device_get(knn.predict(
                state, jnp.int32(int(users[r])), jnp.int32(int(items[r])),
                k=4))
            assert bp[r].tobytes() == np.asarray(sp).tobytes()

    def test_server_batch_equals_server_scalar(self):
        rng = np.random.default_rng(5)
        R = _ratings(rng, 30, 25)
        srv = CFServer(R, ServerConfig(capacity_extra=8))
        users = rng.integers(0, 30, 9)
        batch = srv.recommend_batch(users, n=5, k_neighbors=6)
        for u, row in zip(users, batch):
            assert srv.recommend(int(u), n=5, k_neighbors=6) == row
        items = rng.integers(0, 25, 9)
        preds = srv.predict_batch(users, items, k=6)
        for u, it, p in zip(users, items, preds):
            assert srv.predict(int(u), int(it), k=6) == p


class TestTwinDedup:
    def test_twin_users_share_scores_and_score_once(self):
        """Bitwise-identical rating rows are provably twins: identical
        sims, neighbour lists, and own-row keys -> one scored row."""
        rng = np.random.default_rng(6)
        R = _ratings(rng, 12, 18, density=0.5)
        R[7] = R[3]
        R[9] = R[3]                          # users 3, 7, 9 are twins
        srv = CFServer(R, ServerConfig(capacity_extra=8))
        users = [3, 7, 9, 3, 1, 9]
        q0, u0 = srv.stats.queries, srv.stats.query_unique
        out = srv.recommend_batch(users, n=4, k_neighbors=5)
        assert out[0] == out[1] == out[2] == out[3] == out[5]
        assert srv.stats.queries - q0 == 6
        assert srv.stats.query_unique - u0 < 6         # this batch deduped

    def test_dedup_rows_collapses_only_identical(self):
        rows = np.asarray([[1.0, 2.0], [1.0, 2.0], [1.0, 2.5], [1.0, 2.0]],
                          np.float32)
        plan = dedup_rows(rows)
        assert plan.n_unique == 2
        fanned = fan_out(np.asarray([f"row{i}"
                                     for i in range(plan.n_unique)]), plan)
        assert fanned[0] == fanned[1] == fanned[3]
        assert fanned[2] != fanned[0]

    def test_forced_hash_collision_never_shares_wrongly(self, monkeypatch):
        """Degrade the probe hash to a constant: every row lands in one
        bucket, and only the exact-verify step separates them."""
        monkeypatch.setattr(
            dedup_mod, "_fnv1a",
            lambda cols: np.zeros(cols.shape[0], np.uint32))
        rng = np.random.default_rng(7)
        rows = rng.normal(size=(32, 6)).astype(np.float32)
        rows[5] = rows[2]                    # one genuine twin pair
        plan = dedup_mod.dedup_rows(rows)
        assert plan.n_unique == 31
        rebuilt = plan.unique_rows[plan.scatter]
        assert np.array_equal(rows[rebuilt], rows)
        # end-to-end: server answers are still per-user correct
        R = _ratings(rng, 10, 14)
        srv = CFServer(R, ServerConfig(capacity_extra=4))
        users = list(range(8))
        batch = srv.recommend_batch(users, n=3, k_neighbors=4)
        for u, row in zip(users, batch):
            assert srv.recommend(u, n=3, k_neighbors=4) == row

    def test_distinct_users_not_collapsed(self):
        rng = np.random.default_rng(8)
        R = _ratings(rng, 16, 20, density=0.8)
        srv = CFServer(R, ServerConfig(capacity_extra=4))
        srv.recommend_batch(list(range(16)), n=4, k_neighbors=5)
        # dense distinct rows -> overwhelmingly distinct keys
        assert srv.stats.query_unique >= 15


class TestPerRowGuard:
    def test_mixed_batch_quarantines_and_serves(self):
        rng = np.random.default_rng(9)
        R = _ratings(rng, 20, 15)
        srv = CFServer(R, ServerConfig(capacity_extra=4))
        before = srv.quarantine.total
        out = srv.recommend_batch([4, -1, 10**9, 7, "junk"], n=3,
                                  k_neighbors=5)
        assert out[1] == [] and out[2] == [] and out[4] == []
        assert out[0] == srv.recommend(4, n=3, k_neighbors=5)
        assert out[3] == srv.recommend(7, n=3, k_neighbors=5)
        assert srv.quarantine.total >= before + 3
        assert srv.stats.queries >= 2        # only valid rows counted

    def test_predict_batch_bad_item_row(self):
        rng = np.random.default_rng(10)
        R = _ratings(rng, 12, 10)
        srv = CFServer(R, ServerConfig(capacity_extra=4))
        out = srv.predict_batch([3, 5, 2], [4, 9999, -1], k=4)
        assert out[1] == 0.0 and out[2] == 0.0
        assert out[0] == srv.predict(3, 4, k=4)

    def test_all_invalid_batch_is_cheap_noop(self):
        rng = np.random.default_rng(11)
        srv = CFServer(_ratings(rng, 8, 8), ServerConfig(capacity_extra=4))
        batches_before = srv.stats.query_batches
        assert srv.recommend_batch([-1, 99999]) == [[], []]
        assert srv.predict_batch([-5], [2]) == [0.0]
        assert srv.stats.query_batches == batches_before  # never dispatched


class TestShedDegradesReads:
    def test_shed_serves_reads_at_reduced_k(self):
        rng = np.random.default_rng(12)
        R = _ratings(rng, 20, 16)
        srv = CFServer(R, ServerConfig(capacity_extra=4))
        srv.level = LEVEL_SHED
        out = srv.recommend_batch([1, 2, 3], n=3, k_neighbors=8)
        assert all(len(r) == 3 for r in out)          # served, not refused
        assert srv.stats.query_degraded == 3
        assert srv._query_k(8) == 2                   # 8 // SHED_QUERY_K_DIV
        assert srv._query_k(3) == 1                   # floor at 1
        s = srv.stats.summary()
        for key in ("queries", "query_batches", "query_unique",
                    "query_degraded", "query_p50_ms", "query_p99_ms",
                    "query_dedup_savings"):
            assert key in s

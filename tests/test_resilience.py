"""Fault-injection suite: the CF serving path under hostile conditions.

Contract under test (ISSUE 7 + 8): ``CFServer`` never raises to the
caller — capacity overflow rotates the arena, malformed requests are
quarantined, latency spikes walk the degradation ladder, transient
executor faults retry, and a poisoned arena (bit-flips / simulated shard
loss) is healed from replicas or rolled back to the last good snapshot.
A simulated crash at any injected crash point recovers bit-exactly via
WAL replay over the newest checkpoint; losing any single replica keeps
the server available while re-replication restores redundancy with zero
similarity math.  All faults come from the deterministic harness in
``repro/testing/faults.py``.
"""
from __future__ import annotations

import math
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (RotationPlan, rotate_arena, rotate_arena_frozen,
                        unsorted_rows)
from repro.core.reference import cosine_vs_all_np
from repro.core.similarity import cosine_matrix
from repro.core.types import SENTINEL_GATE
from repro.distributed import (ReplicaState, ReplicatedArena,
                               ReplicationConfig)
from repro.kernels.verify_rows.ops import arena_healthy, rows_sorted_finite
from repro.kernels.verify_rows.ref import rows_sorted_finite_ref
from repro.serving import (CFServer, LadderConfig, RotationConfig,
                           ServerConfig, ServerStats, SnapshotConfig,
                           WalConfig, WriteAheadLog,
                           LEVEL_DEGRADED, LEVEL_SHED, LEVEL_TRADITIONAL,
                           LEVEL_TWINSEARCH)
from repro.serving.guard import RetryPolicy
from repro.testing import (CRASH_POINTS, ROTATION_CRASH_POINTS, FakeClock,
                           Flaky,
                           MalformedRequests, SimulatedCrash,
                           capacity_flood, forbid_similarity_kernels,
                           inject_latency, install_crash, kill_replica,
                           poison_state)
from repro.training import checkpoint
from repro.training.elastic import Action, StragglerMonitor
from tests.conftest import TEST_SEED, make_ratings

pytestmark = pytest.mark.faults


def _unsorted_active(state, n_act):
    """(n_act, n_act) unsorted similarity block recovered from the lists."""
    rows = unsorted_rows(state.sim_vals, state.sim_idx,
                         jnp.arange(n_act, dtype=jnp.int32))
    return np.asarray(rows)[:, :n_act]


# ---------------------------------------------------------------------------
# Guard + quarantine
# ---------------------------------------------------------------------------

class TestGuardQuarantine:
    def test_malformed_onboards_never_raise(self, rng):
        R = make_ratings(rng, n=40, m=16)
        srv = CFServer(R, capacity_extra=8, c_probes=4)
        mal = MalformedRequests(16, seed=1)
        for name, bad in mal.everything():
            uid, info = srv.onboard_user(bad)
            assert uid == -1 and info["status"] == "rejected", name
        assert srv.stats.rejected == 7
        assert srv.quarantine.total == 7
        # one rejection per failure mode, keyed by stable reason strings
        assert set(srv.quarantine.counts) == {
            "non_finite", "shape", "dtype", "range", "empty"}
        # nothing malformed reached the arena: still healthy, still serving
        assert bool(arena_healthy(srv.state.sim_vals, srv.state.ratings,
                                  srv.state.norms, srv.state.n_active))
        uid, info = srv.onboard_user(R[3])
        assert uid == 40 and info["status"] == "ok"

    def test_query_and_update_guards(self, rng):
        R = make_ratings(rng, n=30, m=12)
        srv = CFServer(R, capacity_extra=4)
        assert srv.recommend(-1) == []
        assert srv.recommend(10_000) == []
        assert srv.predict(5, 10_000) == 0.0
        assert not srv.add_rating(5, 3, float("nan"))
        assert not srv.add_rating(5, 3, 99.0)
        assert not srv.add_rating("x", 3, 4.0)
        assert srv.stats.rejected == 6
        assert srv.add_rating(5, 3, 4.0)          # valid still goes through
        assert float(srv.state.ratings[5, 3]) == 4.0

    def test_quarantine_ring_is_bounded(self, rng):
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, capacity_extra=2, quarantine_capacity=5)
        for _ in range(20):
            srv.onboard_user(np.full(10, np.nan, np.float32))
        assert len(srv.quarantine.records) == 5
        assert srv.quarantine.total == 20


# ---------------------------------------------------------------------------
# Arena rotation
# ---------------------------------------------------------------------------

class TestArenaRotation:
    def test_overflow_rotates_instead_of_raising(self, rng):
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, capacity_extra=1)
        srv.onboard_user(R[0])                    # fills the only slot
        uid, info = srv.onboard_user(R[1])        # used to RuntimeError
        assert uid == 21 and info["status"] == "ok"
        assert srv.stats.rotations == 1
        assert srv.n_base == 21 and srv.state.capacity == 22

    def test_flood_past_capacity(self, rng):
        R = make_ratings(rng, n=30, m=12)
        srv = CFServer(R, capacity_extra=4, c_probes=4)
        results = capacity_flood(srv, R, 14, seed=3)
        uids = [u for u, info in results]
        assert all(info["status"] == "ok" for _, info in results)
        assert uids == list(range(30, 44))         # monotonic, no gaps
        assert srv.stats.rotations == 3            # 4-slot arena, 14 users
        assert int(srv.state.n_active) == 44
        recs = srv.recommend(43, n=5)
        assert len(recs) == 5

    def test_rotation_bit_exact_data_movement(self, rng):
        """Rotated base lists must be a pure rearrangement: bitwise equal
        to a numpy re-sort of (gated base entries + recovered buffer sims)
        — no similarity arithmetic happens during rotation."""
        R = make_ratings(rng, n=25, m=12)
        srv = CFServer(R, capacity_extra=4, c_probes=4)
        for i in (3, 7, 3, 11):                    # mix of twins + fresh
            srv.onboard_user(R[i])
        st = srv.state
        n_base, n_act, extra = 25, 29, 4
        U = np.asarray(unsorted_rows(
            st.sim_vals, st.sim_idx,
            jnp.arange(n_base, n_act, dtype=jnp.int32)))
        rot = rotate_arena(st, n_base=n_base, extra=extra)
        assert rot.capacity == n_act + extra
        for x in range(n_base):
            vals = np.asarray(st.sim_vals[x])
            idx = np.asarray(st.sim_idx[x])
            keep = idx < n_base                    # pre-rotation real entries
            ref = np.sort(np.concatenate(
                [vals[keep], U[:, x].astype(vals.dtype)]))
            row = np.asarray(rot.sim_vals[x])
            np.testing.assert_array_equal(row[-ref.shape[0]:], ref)
            ridx = np.asarray(rot.sim_idx[x])
            real = row > SENTINEL_GATE
            assert set(ridx[real]) == set(range(n_act))

    def test_rotated_arena_matches_fresh_traditional_build(self, rng):
        R = make_ratings(rng, n=25, m=12)
        srv = CFServer(R, capacity_extra=5, c_probes=4)
        fresh = make_ratings(np.random.default_rng(7), n=3, m=12)
        for r in (R[3], fresh[0], R[3], fresh[1], fresh[2]):
            srv.onboard_user(r)
        srv.onboard_user(R[8])                     # triggers rotation
        assert srv.stats.rotations == 1
        n_act = int(srv.state.n_active)
        S_ref = np.asarray(cosine_matrix(srv.state.ratings[:n_act]))
        # The compacted region (everything rotated into the base) is
        # all-pairs complete and matches a fresh traditional build ...
        nb = srv.n_base
        S_rot = _unsorted_active(srv.state, n_act)
        np.testing.assert_allclose(S_rot[:nb, :nb], S_ref[:nb, :nb],
                                   atol=1e-5)
        np.testing.assert_allclose(S_rot[nb:, :nb], S_ref[nb:, :nb],
                                   atol=1e-5)      # new rows vs base
        # ... and the post-rotation onboard's deferred symmetric entries
        # land on the next compaction: rotating once more yields the full
        # fresh matrix.
        full = rotate_arena(srv.state, n_base=nb, extra=0)
        S_full = _unsorted_active(full, n_act)
        np.testing.assert_allclose(S_full, S_ref, atol=1e-5)
        # rows stay ascending and healthy after rotation
        assert bool(arena_healthy(srv.state.sim_vals, srv.state.ratings,
                                  srv.state.norms, srv.state.n_active))

    def test_rotation_gates_refreshed_rows(self, rng):
        """A base row re-sorted by add_rating already contains write-region
        entries; rotation must not duplicate them."""
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, capacity_extra=2, c_probes=4)
        srv.onboard_user(R[2])
        srv.add_rating(5, 3, 4.0)                  # row 5 now sees user 20
        srv.onboard_user(R[6])                     # fills the arena
        srv.onboard_user(R[9])                     # rotates, then onboards
        assert srv.stats.rotations == 1
        idx = np.asarray(srv.state.sim_idx)
        vals = np.asarray(srv.state.sim_vals)
        n_act = int(srv.state.n_active)
        for x in range(n_act):
            real = idx[x][vals[x] > SENTINEL_GATE]
            assert len(real) == len(set(real)), f"duplicate ids in row {x}"


# ---------------------------------------------------------------------------
# Degradation ladder (latency spikes, virtual time)
# ---------------------------------------------------------------------------

class TestDegradationLadder:
    def _server(self, R, clock, **kw):
        mon = StragglerMonitor(window=20, straggler_ratio=2.0,
                               hang_timeout_s=1000.0,
                               consecutive_to_shrink=2, clock=clock)
        return CFServer(R, capacity_extra=64, c_probes=4, monitor=mon,
                        snapshot_every=10_000, check_every=10_000, **kw)

    def test_spikes_step_down_ladder_then_recover(self, rng):
        R = make_ratings(rng, n=40, m=16)
        clock = FakeClock()
        srv = self._server(R, clock, recover_after=5, shed_cooldown_s=10.0)
        inject_latency(srv, clock, [0.1] * 12 + [1.0] * 4 + [0.1] * 30)
        for i in range(16):
            _, info = srv.onboard_user(R[i % 40])
            assert info["status"] == "ok"
        # two straggler verdicts: twinsearch -> traditional -> shed (the
        # latency walk skips the replica-owned ``degraded`` rung)
        assert srv.stats.degradations == 2
        assert srv.level == LEVEL_SHED

        # shed: backpressure, no work, no raise
        uid, info = srv.onboard_user(R[0])
        assert uid == -1 and info["status"] == "shed"
        assert info["retry_after_s"] > 0
        assert srv.stats.shed == 1

        # cooldown expiry probes traditional again, healthy streak recovers
        clock.advance(11.0)
        _, info = srv.onboard_user(R[0])
        assert info["status"] == "ok" and srv.level == LEVEL_TRADITIONAL
        for i in range(6):
            srv.onboard_user(R[i])
        assert srv.level == LEVEL_TWINSEARCH
        assert srv.stats.recoveries == 2

    def test_hang_sheds_immediately(self, rng):
        R = make_ratings(rng, n=40, m=16)
        clock = FakeClock()
        mon = StragglerMonitor(window=20, straggler_ratio=2.0,
                               hang_timeout_s=5.0,
                               consecutive_to_shrink=2, clock=clock)
        srv = CFServer(R, capacity_extra=16, c_probes=4, monitor=mon,
                       snapshot_every=10_000, check_every=10_000)
        inject_latency(srv, clock, [0.1] * 10 + [60.0])
        for i in range(10):
            srv.onboard_user(R[i])
        assert srv.level == LEVEL_TWINSEARCH
        _, info = srv.onboard_user(R[10])          # hang-scale latency
        assert info["status"] == "ok"              # the call did finish...
        assert srv.level == LEVEL_SHED             # ...but ABORT -> shed


# ---------------------------------------------------------------------------
# Retry / transient executor faults
# ---------------------------------------------------------------------------

class TestRetry:
    def test_transient_fault_retries_to_success(self, rng):
        R = make_ratings(rng, n=30, m=12)
        srv = CFServer(R, capacity_extra=4,
                       retry=RetryPolicy(max_attempts=4, base_delay_s=1e-4,
                                         deadline_s=10.0,
                                         sleep=lambda s: None))
        srv._onboard = Flaky(srv._onboard, fail_times=2)
        uid, info = srv.onboard_user(R[0])
        assert uid == 30 and info["status"] == "ok"
        assert srv.stats.retries == 2

    def test_permanent_fault_is_quarantined_not_raised(self, rng):
        R = make_ratings(rng, n=30, m=12)
        srv = CFServer(R, capacity_extra=4,
                       retry=RetryPolicy(max_attempts=3, base_delay_s=1e-4,
                                         deadline_s=10.0,
                                         sleep=lambda s: None))
        srv._onboard = Flaky(srv._onboard, fail_times=99)
        uid, info = srv.onboard_user(R[0])
        assert uid == -1 and info["status"] == "error"
        assert srv.stats.errors == 1
        assert srv.quarantine.counts["error"] == 1
        # state untouched by the failed attempts (functional updates)
        assert int(srv.state.n_active) == 30
        srv._build_jits()                          # drop the fault wrapper
        uid, info = srv.onboard_user(R[0])
        assert uid == 30 and info["status"] == "ok"


# ---------------------------------------------------------------------------
# Snapshot / rollback (state poisoning, simulated shard loss)
# ---------------------------------------------------------------------------

class TestSnapshotRollback:
    def test_poisoned_lists_roll_back(self, rng, tmp_path):
        R = make_ratings(rng, n=30, m=12)
        srv = CFServer(R, capacity_extra=8, snapshot_every=3, check_every=1,
                       snapshot_dir=str(tmp_path))
        for i in range(4):
            srv.onboard_user(R[i])
        good_n = int(srv.state.n_active)
        assert checkpoint.all_steps(str(tmp_path))  # disk snapshots landed

        poison_state(srv, rows=[2, 17])            # bit-flip corruption
        uid, info = srv.onboard_user(R[5])
        assert uid == -1 and info["status"] == "rolled_back"
        assert srv.stats.rollbacks == 1
        assert int(srv.state.n_active) <= good_n
        assert bool(arena_healthy(srv.state.sim_vals, srv.state.ratings,
                                  srv.state.norms, srv.state.n_active))
        uid, info = srv.onboard_user(R[5])         # back in business
        assert info["status"] == "ok"
        assert len(srv.recommend(int(srv.state.n_active) - 1, n=3)) == 3

    def test_simulated_shard_loss_rolls_back(self, rng):
        R = make_ratings(rng, n=32, m=12)
        srv = CFServer(R, capacity_extra=8, snapshot_every=2, check_every=1)
        for i in range(3):
            srv.onboard_user(R[i])
        # shard 2 of 4 dies; its row-shard of the ratings arena is garbage
        lost = poison_state(srv, shard=2, n_shards=4, field="ratings")
        assert lost.shape[0] == 10                 # 40-row arena / 4
        uid, info = srv.onboard_user(R[7])
        assert uid == -1 and info["status"] == "rolled_back"
        assert srv.stats.rollbacks == 1
        uid, info = srv.onboard_user(R[7])
        assert info["status"] == "ok"

    def test_rollback_across_rotation_restores_geometry(self, rng):
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, capacity_extra=2, snapshot_every=10_000,
                       check_every=1)
        cap0, nb0 = srv.state.capacity, srv.n_base
        for i in range(5):                         # forces rotations
            srv.onboard_user(R[i])
        assert srv.stats.rotations >= 1
        assert srv.state.capacity > cap0
        poison_state(srv, rows=[1])
        _, info = srv.onboard_user(R[6])
        assert info["status"] == "rolled_back"
        # only the construction snapshot existed: geometry rolled back too
        assert srv.state.capacity == cap0 and srv.n_base == nb0
        _, info = srv.onboard_user(R[6])
        assert info["status"] == "ok"


# ---------------------------------------------------------------------------
# Invariant-check op (verify_rows family)
# ---------------------------------------------------------------------------

class TestHealthOp:
    def test_rows_sorted_finite_matches_ref(self, rng):
        vals = np.sort(rng.normal(size=(8, 16)).astype(np.float32), axis=1)
        vals[2, 5] = np.nan                        # live + non-finite
        vals[4, 3], vals[4, 4] = vals[4, 4], vals[4, 3]   # live + unsorted
        vals[6, 0] = np.inf                        # unsorted AND non-finite
        live = np.arange(8) < 7                    # row 7 is dead
        vals[7, :] = np.nan                        # dead rows never flag
        got = np.asarray(rows_sorted_finite(jnp.asarray(vals), jnp.int32(7)))
        ref = np.asarray(rows_sorted_finite_ref(jnp.asarray(vals),
                                                jnp.asarray(live)))
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(
            got, [True, True, False, True, False, True, False, True])

    def test_arena_healthy_gates(self, rng):
        R = make_ratings(rng, n=16, m=8)
        srv = CFServer(R, capacity_extra=2)
        st = srv.state
        ok = lambda s: bool(arena_healthy(s.sim_vals, s.ratings, s.norms,
                                          s.n_active))
        assert ok(st)
        assert not ok(st._replace(
            norms=st.norms.at[3].set(jnp.float32(jnp.nan))))
        assert not ok(st._replace(n_active=jnp.int32(99)))
        bad = st.sim_vals.at[0, 0].set(jnp.float32(5.0))   # > all: unsorted
        assert not ok(st._replace(sim_vals=bad))


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_onboard_ms_is_bounded_ring(self):
        stats = ServerStats(latency_window=8)
        for i in range(100):
            stats.onboard_ms.append(float(i))
        assert len(stats.onboard_ms) == 8
        s = stats.summary()
        # percentiles over the trailing window only (92..99)
        assert s["onboard_p50_ms"] == 96.0
        assert s["onboard_p99_ms"] == 99.0

    def test_straggler_finish_without_start(self):
        mon = StragglerMonitor()
        assert mon.step_finished() is Action.CONTINUE
        assert mon.stats() == {}                   # no sample recorded
        mon.step_started()
        assert mon.step_finished() is Action.CONTINUE
        assert mon.step_finished() is Action.CONTINUE   # double-finish too
        assert mon.stats()["n"] == 1

    def test_add_rating_jit_hoisted(self, rng):
        R = make_ratings(rng, n=12, m=8)
        srv = CFServer(R, capacity_extra=2)
        # jits exist before any call — a first-call failure can't leave the
        # server half-initialised
        for attr in ("_add", "_init_cache", "_onboard", "_onboard_trad",
                     "_recommend", "_predict"):
            assert hasattr(srv, attr), attr
        assert srv._cache is None                  # cache itself stays lazy


# ---------------------------------------------------------------------------
# Write-ahead log (unit)
# ---------------------------------------------------------------------------

class TestWriteAheadLog:
    def test_roundtrip_bit_exact(self, tmp_path, rng):
        wal = WriteAheadLog(str(tmp_path))
        r = rng.normal(size=(16,)).astype(np.float32)
        p = rng.integers(0, 40, size=4).astype(np.int32)
        wal.append(1, "onboard", {"use_twin": True},
                   {"ratings": r, "probes": p})
        wal.append(2, "add_rating", {"user": 3, "item": 5, "rating": 4.0})
        wal.append(3, "rotate")
        wal.close()

        wal2 = WriteAheadLog(str(tmp_path))        # reopen
        recs = wal2.records()
        assert [x.seq for x in recs] == [1, 2, 3]
        assert [x.op for x in recs] == ["onboard", "add_rating", "rotate"]
        np.testing.assert_array_equal(recs[0].arrays["ratings"], r)
        np.testing.assert_array_equal(recs[0].arrays["probes"], p)
        assert recs[0].fields == {"use_twin": True}
        assert recs[1].fields["rating"] == 4.0

    def test_torn_tail_is_repaired(self, tmp_path, rng):
        wal = WriteAheadLog(str(tmp_path))
        for s in range(1, 4):
            wal.append(s, "add_rating", {"user": s, "item": 0,
                                         "rating": 1.0})
        wal.close()
        # tear the tail mid-record, as a crash mid-append would
        with open(wal.path, "r+b") as f:
            f.truncate(wal.size_bytes() - 7)
        wal2 = WriteAheadLog(str(tmp_path))
        assert [x.seq for x in wal2.records()] == [1, 2]
        wal2.append(3, "rotate")                   # appendable after repair
        assert [x.seq for x in wal2.records()] == [1, 2, 3]

    def test_truncation_policies(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path))
        for s in range(1, 6):
            wal.append(s, "rotate")
        wal.truncate_through(3)                    # durable snapshot at 3
        assert [x.seq for x in wal.records()] == [4, 5]
        wal.truncate_after(4)                      # rollback to 4
        assert [x.seq for x in wal.records()] == [4]
        assert wal.truncations == 2

    def test_aborted_ops_are_filtered(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path))
        wal.append(1, "onboard", {"use_twin": False})
        wal.append(2, "onboard", {"use_twin": False})
        wal.append(3, "abort", {"target": 2})      # op 2 failed after log
        wal.append(4, "rotate")
        assert [x.seq for x in wal.records()] == [1, 4]

    def test_fsync_off_still_readable(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path), fsync=False)
        wal.append(1, "rotate")
        assert len(WriteAheadLog(str(tmp_path)).records()) == 1

    def test_raw_bounds_include_aborts(self, tmp_path):
        """first_seq/last_seq are raw bounds: aborted ops and their
        compensation records consumed sequence numbers even though
        records() filters them from the replay stream."""
        wal = WriteAheadLog(str(tmp_path))
        assert (wal.first_seq, wal.last_seq) == (0, 0)
        wal.append(1, "onboard", {"use_twin": False})
        wal.append(2, "abort", {"target": 1})
        assert (wal.first_seq, wal.last_seq) == (1, 2)
        wal.close()
        wal2 = WriteAheadLog(str(tmp_path))        # bounds survive reopen
        assert (wal2.first_seq, wal2.last_seq) == (1, 2)
        assert wal2.records() == []                # yet nothing replays

    def test_truncate_after_rewinds_last_seq(self, tmp_path):
        """Rollback truncation rewinds last_seq to the rollback point so
        discarded seqs are reissued — even when every record is dropped."""
        wal = WriteAheadLog(str(tmp_path))
        for s in range(1, 6):
            wal.append(s, "rotate")
        wal.truncate_after(2)
        assert wal.last_seq == 2
        wal.truncate_after(0)                      # drops every record
        assert (wal.first_seq, wal.last_seq) == (0, 0)

    def test_truncate_through_keeps_last_seq(self, tmp_path):
        """Checkpoint truncation un-consumes nothing: last_seq holds even
        when the log empties, so numbering never restarts over old seqs."""
        wal = WriteAheadLog(str(tmp_path))
        for s in range(1, 4):
            wal.append(s, "rotate")
        wal.truncate_through(3)                    # empties the log
        assert (wal.first_seq, wal.last_seq) == (0, 3)
        wal.append(4, "rotate")
        assert (wal.first_seq, wal.last_seq) == (4, 4)


# ---------------------------------------------------------------------------
# Checkpoint CRC (satellite)
# ---------------------------------------------------------------------------

class TestCheckpointCRC:
    def _tree(self, rng, shift=0.0):
        return {"a": jnp.asarray(rng.normal(size=(8, 8)) + shift,
                                 jnp.float32),
                "b": jnp.asarray(np.arange(16), jnp.int32)}

    def _corrupt_leaf(self, ckpt_dir, step, fname="a.npy"):
        path = os.path.join(ckpt_dir, f"step_{step:010d}", fname)
        with open(path, "r+b") as f:
            f.seek(-4, os.SEEK_END)                # flip data bytes, keep
            f.write(b"\xde\xad\xbe\xef")           # the .npy header valid

    def test_corrupt_leaf_falls_back_to_previous_step(self, tmp_path, rng):
        d = str(tmp_path)
        t1 = self._tree(rng)
        t2 = self._tree(rng, shift=1.0)
        checkpoint.save(d, 1, t1)
        checkpoint.save(d, 2, t2)
        self._corrupt_leaf(d, 2)
        tree, step, _ = checkpoint.restore(d, t1)
        assert step == 1                           # newest was corrupt
        np.testing.assert_array_equal(np.asarray(tree["a"]),
                                      np.asarray(t1["a"]))

    def test_explicit_step_raises_on_corruption(self, tmp_path, rng):
        d = str(tmp_path)
        t = self._tree(rng)
        checkpoint.save(d, 1, t)
        self._corrupt_leaf(d, 1)
        with pytest.raises(checkpoint.CorruptCheckpointError):
            checkpoint.restore(d, t, step=1)

    def test_all_corrupt_raises(self, tmp_path, rng):
        d = str(tmp_path)
        t = self._tree(rng)
        checkpoint.save(d, 1, t)
        checkpoint.save(d, 2, t)
        self._corrupt_leaf(d, 1)
        self._corrupt_leaf(d, 2)
        with pytest.raises(checkpoint.CorruptCheckpointError):
            checkpoint.restore(d, t)

    def test_missing_leaf_file_is_corruption(self, tmp_path, rng):
        d = str(tmp_path)
        t = self._tree(rng)
        checkpoint.save(d, 1, t)
        checkpoint.save(d, 2, t)
        os.remove(os.path.join(d, "step_0000000002", "a.npy"))
        _, step, _ = checkpoint.restore(d, t)
        assert step == 1


# ---------------------------------------------------------------------------
# Crash + WAL recovery (tentpole): kill-and-restart is bit-exact
# ---------------------------------------------------------------------------

def _assert_states_equal(a, b):
    for f in ("ratings", "norms", "sim_vals", "sim_idx"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"field {f} diverged")
    assert int(a.n_active) == int(b.n_active)


class TestCrashRecovery:
    KNOBS = dict(capacity_extra=6, c_probes=4, snapshot_every=5,
                 check_every=3)

    def _server(self, R, tmp_path, tag, **extra):
        return CFServer(R, wal_dir=str(tmp_path / f"{tag}-wal"),
                        snapshot_dir=str(tmp_path / f"{tag}-snap"),
                        **{**self.KNOBS, **extra})

    def _pool(self, rng, R):
        fresh = make_ratings(np.random.default_rng(101), n=6, m=R.shape[1])
        # mix of twins (base copies) and fresh rows: both onboard paths
        return np.concatenate([R[:3], fresh, R[5:8]], axis=0)

    @pytest.mark.parametrize("point,nth", [
        ("onboard.pre_wal", 4),
        ("onboard.post_wal", 4),
        ("onboard.post_commit", 4),
        ("rotate.post_wal", 1),                 # fires at the 7th onboard
    ])
    def test_kill_and_restart_bit_exact(self, rng, tmp_path, point, nth):
        """A crash at any injected crash point mid-sequence, recovered via
        checkpoint + WAL replay, converges to the exact same arena as an
        uncrashed run over the same request sequence."""
        R = make_ratings(rng, n=40, m=16)
        pool = self._pool(rng, R)
        n_ops = 10                              # > capacity_extra: rotates

        oracle = self._server(R, tmp_path, "oracle")
        for i in range(n_ops):
            _, info = oracle.onboard_user(pool[i % len(pool)])
            assert info["status"] == "ok"

        victim = self._server(R, tmp_path, "victim")
        install_crash(victim, point, nth=nth)
        crashed = False
        for i in range(n_ops):
            try:
                victim.onboard_user(pool[i % len(pool)])
            except SimulatedCrash as e:
                assert e.point == point
                crashed = True
                break
        assert crashed, f"crash point {point} never fired"

        recovered = CFServer.recover(
            R, wal_dir=str(tmp_path / "victim-wal"),
            snapshot_dir=str(tmp_path / "victim-snap"), **self.KNOBS)
        # ops already applied (WAL-replayed or checkpointed) must not be
        # re-issued; everything else is, as a client retry would
        applied = int(recovered.state.n_active) - 40
        for i in range(applied, n_ops):
            _, info = recovered.onboard_user(pool[i % len(pool)])
            assert info["status"] == "ok"

        _assert_states_equal(recovered.state, oracle.state)
        assert recovered.n_base == oracle.n_base
        assert recovered.state.capacity == oracle.state.capacity
        # and the recovered server keeps serving identically
        assert recovered.recommend(5, n=5) == oracle.recommend(5, n=5)

    @pytest.mark.parametrize("point,applied", [
        ("add_rating.pre_wal", False),          # op lost: not yet logged
        ("add_rating.post_wal", True),          # logged: replay applies it
        ("add_rating.post_commit", True),
    ])
    def test_crash_around_add_rating(self, rng, tmp_path, point, applied):
        R = make_ratings(rng, n=30, m=12)
        oracle = self._server(R, tmp_path, "oracle")
        for i in range(3):
            oracle.onboard_user(R[i])
        if applied:
            assert oracle.add_rating(2, 3, 4.0)

        victim = self._server(R, tmp_path, "victim")
        for i in range(3):
            victim.onboard_user(R[i])
        install_crash(victim, point)
        with pytest.raises(SimulatedCrash):
            victim.add_rating(2, 3, 4.0)

        recovered = CFServer.recover(
            R, wal_dir=str(tmp_path / "victim-wal"),
            snapshot_dir=str(tmp_path / "victim-snap"), **self.KNOBS)
        _assert_states_equal(recovered.state, oracle.state)

    def test_recovery_with_wal_only(self, rng, tmp_path):
        """No disk checkpoints at all: replay runs over a fresh build of
        the same base ratings and still lands bit-exact."""
        R = make_ratings(rng, n=30, m=12)
        knobs = dict(capacity_extra=6, c_probes=4,
                     wal_dir=str(tmp_path / "wal"))
        srv = CFServer(R, **knobs)
        for i in range(8):                      # crosses one rotation
            srv.onboard_user(R[i])
        srv.add_rating(1, 2, 3.0)
        ref = srv.state

        recovered = CFServer.recover(R, **knobs)
        assert recovered.stats.wal_replayed == len(srv.wal.records())
        _assert_states_equal(recovered.state, ref)

    def test_aborted_onboard_not_replayed(self, rng, tmp_path):
        """An onboard that failed after its WAL append leaves an abort
        record; recovery must skip it."""
        R = make_ratings(rng, n=30, m=12)
        srv = self._server(R, tmp_path, "victim",
                           retry=RetryPolicy(max_attempts=2,
                                             base_delay_s=1e-4,
                                             deadline_s=10.0,
                                             sleep=lambda s: None))
        srv.onboard_user(R[0])
        srv._onboard = Flaky(srv._onboard, fail_times=99)
        _, info = srv.onboard_user(R[1])
        assert info["status"] == "error"
        srv._build_jits()                       # drop the fault wrapper
        srv.onboard_user(R[2])
        ref = srv.state

        recovered = CFServer.recover(
            R, wal_dir=str(tmp_path / "victim-wal"),
            snapshot_dir=str(tmp_path / "victim-snap"), **self.KNOBS)
        _assert_states_equal(recovered.state, ref)

    _FAST_RETRY = dict(max_attempts=2, base_delay_s=1e-4, deadline_s=10.0,
                       sleep=lambda s: None)

    def test_aborted_tail_never_reuses_seqs(self, rng, tmp_path):
        """Crash right after an onboard aborts: the WAL tail is the abort
        record.  Recovery must resume numbering past it — reissuing the
        aborted seq would make records() drop the next committed op as
        aborted on a later recovery, silently losing an acked mutation."""
        R = make_ratings(rng, n=30, m=12)
        srv = self._server(R, tmp_path, "victim",
                           retry=RetryPolicy(**self._FAST_RETRY))
        srv.onboard_user(R[0])
        srv._onboard = Flaky(srv._onboard, fail_times=99)
        _, info = srv.onboard_user(R[1])
        assert info["status"] == "error"            # WAL tail = abort

        r1 = CFServer.recover(
            R, wal_dir=str(tmp_path / "victim-wal"),
            snapshot_dir=str(tmp_path / "victim-snap"), **self.KNOBS)
        assert r1._seq >= r1.wal.last_seq           # numbering moved past it
        _, info = r1.onboard_user(R[2])             # committed + acked
        assert info["status"] == "ok"
        ref = r1.state

        r2 = CFServer.recover(                      # second kill-and-restart
            R, wal_dir=str(tmp_path / "victim-wal"),
            snapshot_dir=str(tmp_path / "victim-snap"), **self.KNOBS)
        _assert_states_equal(r2.state, ref)

    def test_wal_only_recovery_with_aborted_first_op(self, rng, tmp_path):
        """No checkpoints + the first logged op aborted: recovery must not
        mistake the abort-filtered prefix for a truncated one."""
        R = make_ratings(rng, n=30, m=12)
        knobs = dict(capacity_extra=6, c_probes=4,
                     wal_dir=str(tmp_path / "wal"))
        srv = CFServer(R, retry=RetryPolicy(**self._FAST_RETRY), **knobs)
        srv._onboard = Flaky(srv._onboard, fail_times=99)
        _, info = srv.onboard_user(R[0])
        assert info["status"] == "error"            # seq 1 aborted
        srv._build_jits()                           # drop the fault wrapper
        srv.onboard_user(R[1])
        ref = srv.state

        recovered = CFServer.recover(R, **knobs)    # must not raise
        _assert_states_equal(recovered.state, ref)

    @pytest.mark.parametrize("snapshot_every,wal_empty", [
        (2, True),      # WAL truncated through the corrupt newest step
        (4, False),     # WAL holds a suffix, but past the gap
    ])
    def test_fallback_over_truncated_wal_fails_loudly(self, rng, tmp_path,
                                                      snapshot_every,
                                                      wal_empty):
        """Newest checkpoint corrupt after the WAL was truncated through
        it: the ops between the fallback step and the corrupt one are
        unrecoverable, and recovery must raise instead of silently
        replaying over the gap (JAX's clamped indexing would corrupt rows
        without a trace)."""
        R = make_ratings(rng, n=30, m=12)
        srv = self._server(R, tmp_path, "victim",
                           snapshot_every=snapshot_every)
        for i in range(6):
            _, info = srv.onboard_user(R[i])
            assert info["status"] == "ok"
        assert (len(srv.wal.records()) == 0) == wal_empty

        snap = tmp_path / "victim-snap"
        steps = checkpoint.all_steps(str(snap))
        assert len(steps) >= 2
        step_dir = snap / f"step_{steps[-1]:010d}"
        leaf = next(p for p in sorted(step_dir.iterdir())
                    if p.suffix == ".npy")
        with open(leaf, "r+b") as f:                # flip data bytes, keep
            f.seek(-4, os.SEEK_END)                 # the .npy header valid
            f.write(b"\xde\xad\xbe\xef")

        with pytest.raises(RuntimeError, match="gap|truncated"):
            CFServer.recover(
                R, wal_dir=str(tmp_path / "victim-wal"),
                snapshot_dir=str(tmp_path / "victim-snap"),
                **{**self.KNOBS, "snapshot_every": snapshot_every})

    def test_recovery_converges_after_repeated_crashes(self, rng, tmp_path):
        """Crash -> recover -> crash again during recovery-adjacent ops:
        the WAL + checkpoint pair is idempotent."""
        R = make_ratings(rng, n=30, m=12)
        srv = self._server(R, tmp_path, "victim")
        for i in range(4):
            srv.onboard_user(R[i])
        for _ in range(3):                      # repeated kill-and-restart
            srv = CFServer.recover(
                R, wal_dir=str(tmp_path / "victim-wal"),
                snapshot_dir=str(tmp_path / "victim-snap"), **self.KNOBS)
        oracle = self._server(R, tmp_path, "oracle")
        for i in range(4):
            oracle.onboard_user(R[i])
        _assert_states_equal(srv.state, oracle.state)


# ---------------------------------------------------------------------------
# Replication: replica kill, failover reads, re-replication (tentpole)
# ---------------------------------------------------------------------------

class TestReplication:
    def test_placement_chained_declustering(self):
        cfg = ReplicationConfig(n_shards=4, r=2)
        assert cfg.owners(0) == (0, 1)
        assert cfg.owners(3) == (3, 0)
        # any single node loss leaves every shard a survivor
        for node in range(4):
            for s in range(4):
                assert any(n != node for n in cfg.owners(s))

    @pytest.mark.parametrize("node", [0, 1, 2, 3])
    def test_any_single_replica_down_stays_available(self, rng, node):
        """Acceptance: with any single node down (its replicas gone AND
        its primary shard rows garbage) the server answers identically,
        heals from survivors, and restores r-way redundancy — all without
        a single similarity-kernel call."""
        R = make_ratings(rng, n=40, m=16)
        srv = CFServer(R, capacity_extra=8, c_probes=4,
                       replication=ReplicationConfig(n_shards=4, r=2))
        for i in range(4):
            srv.onboard_user(R[i])
        users = [1, 11, 21, 31, 41]
        before = {u: srv.recommend(u, n=5) for u in users}

        forbid_similarity_kernels(srv)          # recovery = data movement
        kill_replica(srv, node)
        assert srv.replicas.degraded()

        after = {u: srv.recommend(u, n=5) for u in users}
        assert after == before                  # correct top-n, no raise
        assert srv.stats.repairs >= 1           # healed, not rolled back
        assert srv.stats.rollbacks == 0
        assert srv.replicas.redundancy() == 2   # re-replication completed
        assert srv.replicas.rebuilt_rows > 0

    def test_degraded_rung_pins_ladder_until_redundancy_restored(self, rng):
        R = make_ratings(rng, n=40, m=16)
        srv = CFServer(R, capacity_extra=8, c_probes=4, recover_after=1,
                       replication=ReplicationConfig(n_shards=4, r=2,
                                                     rebuild_rows=5))
        srv.onboard_user(R[0])
        assert srv.level == LEVEL_TWINSEARCH
        srv.replicas.kill_node(2)               # replicas only; primary ok
        _, info = srv.onboard_user(R[1])
        assert info["status"] == "ok"
        assert srv.level == LEVEL_DEGRADED      # rung entered
        assert info["level"] == "degraded"
        assert not info["twin_found"]           # degraded = traditional path

        # budgeted rebuild: a few ticks to copy 2 replicas x 12 rows
        seen_degraded = 0
        for _ in range(8):
            srv.recommend(1, n=3)
            if srv.replicas.degraded():
                seen_degraded += 1
        assert seen_degraded >= 2               # budget made it incremental
        assert srv.replicas.redundancy() == 2
        assert srv.level == LEVEL_TRADITIONAL   # rung released on restore
        _, info = srv.onboard_user(R[2])        # healthy streak recovers
        assert srv.level == LEVEL_TWINSEARCH

    def test_unrecoverable_rows_fall_back_to_rollback(self, rng):
        """r=1 (no redundancy): losing the only replica of a shard leaves
        poison unrecoverable — the PR 2 rollback remains the backstop and
        the server stays pinned degraded but available."""
        R = make_ratings(rng, n=40, m=16)
        srv = CFServer(R, capacity_extra=8, c_probes=4, check_every=1,
                       replication=ReplicationConfig(n_shards=4, r=1))
        srv.onboard_user(R[0])
        kill_replica(srv, 2)                    # poisons primary shard 2
        _, info = srv.onboard_user(R[1])
        assert info["status"] == "rolled_back"
        assert srv.stats.rollbacks == 1
        assert srv.level == LEVEL_DEGRADED      # dead replica never revives
        _, info = srv.onboard_user(R[1])
        assert info["status"] == "ok"           # still serving

    def test_rebuilding_replica_absorbs_writes(self, rng):
        """Writes landing mid-rebuild must not be lost: rows already
        copied take them directly, later rows pick them up from the
        (already-updated) source replica."""
        R = make_ratings(rng, n=40, m=16)
        srv = CFServer(R, capacity_extra=8, c_probes=4,
                       replication=ReplicationConfig(n_shards=4, r=2,
                                                     rebuild_rows=3))
        srv.replicas.kill_node(1)
        while srv.replicas.degraded():
            srv.add_rating(int(rng.integers(0, 40)),
                           int(rng.integers(0, 16)), 4.0)
        # every replica copy now mirrors the primary bit-exactly
        for (n, s), rep in srv.replicas._replicas.items():
            assert rep.state is ReplicaState.HEALTHY
            sl = srv.replicas._slices[s]
            for f in ("ratings", "norms", "sim_vals", "sim_idx"):
                np.testing.assert_array_equal(
                    rep.data[f], np.asarray(getattr(srv.state, f))[sl],
                    err_msg=f"replica ({n},{s}) field {f}")

    def test_replica_sweep_catches_silent_corruption(self, rng):
        R = make_ratings(rng, n=40, m=16)
        srv = CFServer(R, capacity_extra=8, check_every=2,
                       replication=ReplicationConfig(n_shards=4, r=2))
        rep = srv.replicas._replicas[(1, 1)]
        rep.data["sim_vals"][0, 0] = np.nan     # silent replica bit-flip
        for i in range(3):                      # check_every sweeps it
            srv.onboard_user(R[i])
        assert srv.replicas._replicas[(1, 1)].state is not \
            ReplicaState.HEALTHY or srv.replicas.rebuilt_rows > 0
        assert srv.replicas.dead_marks >= 1

    def test_rotation_resets_replicas_to_new_geometry(self, rng):
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, capacity_extra=4, c_probes=4,
                       replication=ReplicationConfig(n_shards=4, r=2))
        for i in range(6):                      # forces a rotation
            srv.onboard_user(R[i])
        assert srv.stats.rotations >= 1
        assert srv.replicas.n_rows == srv.state.capacity
        # replicas mirror the rotated arena; a kill is still recoverable
        kill_replica(srv, 0)
        assert srv.recommend(3, n=3)
        assert srv.stats.rollbacks == 0


# ---------------------------------------------------------------------------
# Rotation hysteresis (satellite)
# ---------------------------------------------------------------------------

class TestRotationHysteresis:
    def test_headroom_grows_write_region(self, rng):
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, capacity_extra=4, c_probes=4, rotate_headroom=2.0)
        for i in range(5):
            srv.onboard_user(R[i])
        assert srv.stats.rotations == 1
        # absorbed burst k=4, headroom 2.0 -> fresh write region 8, not 4
        assert srv.k_cap == 8
        assert srv.state.capacity == 24 + 8

    def test_headroom_reduces_rotation_count(self, rng):
        R = make_ratings(rng, n=20, m=10)
        flat = CFServer(R, capacity_extra=4, c_probes=4)
        grow = CFServer(R, capacity_extra=4, c_probes=4,
                        rotate_headroom=2.0)
        for i in range(20):
            flat.onboard_user(R[i % 20])
            grow.onboard_user(R[i % 20])
        assert grow.stats.rotations < flat.stats.rotations

    def test_rotation_duration_lands_in_stats(self, rng):
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, capacity_extra=2, c_probes=4)
        for i in range(5):
            srv.onboard_user(R[i])
        assert srv.stats.rotations >= 1
        assert len(srv.stats.rotation_ms) == srv.stats.rotations
        s = srv.stats.summary()
        assert s["rotation_max_ms"] > 0.0
        assert "rotation_p50_ms" in s


# ---------------------------------------------------------------------------
# Incremental (chunked, resumable) rotation — ISSUE 9 tentpole
# ---------------------------------------------------------------------------

# Floods through incremental rotation: name -> (base users, items,
# capacity_extra, budget_rows, onboards, stream).  "tiny" is the original
# pool of copies and fresh rows; the others are a small Douban-like
# catalogue, where a budget of 64 rows finishes each plan within the
# reserve of 4 slots (4 x 64 >= n_base) and 16 does not ("starved").
FLOODS = {
    "tiny": (24, 12, 4, 6, 12, "pool"),
    "copy_of_base": (200, 500, 16, 64, 56, "copy_of_base"),
    "fresh": (200, 500, 16, 64, 56, "fresh"),
    "starved": (200, 500, 16, 16, 56, "copy_of_base"),
}


def _flood_rows(R, stream: str, count: int, rng) -> np.ndarray:
    """``count`` onboard rows: the tiny pool, copies of uniformly drawn
    base users, or fresh profiles."""
    if stream == "pool":
        fresh = make_ratings(np.random.default_rng(77), n=6, m=R.shape[1])
        pool = np.concatenate([R[:4], fresh, R[8:12]], axis=0)
        return pool[np.arange(count) % len(pool)]
    if stream == "copy_of_base":
        return R[rng.integers(0, R.shape[0], count)].copy()
    return make_ratings(rng, n=count, m=R.shape[1], density=0.05)


@pytest.fixture(scope="module")
def flood(tmp_path_factory):
    """``flood(case)``: a synchronous server and an incremental one (with
    a WAL) fed the same onboards, made once per case.  Both hold their
    straggler monitors off: on a loaded host the calls that compile could
    walk one server's ladder to the traditional path, and the two would
    no longer run the same program."""
    runs = {}

    def quiet():
        return LadderConfig(monitor=StragglerMonitor(
            window=64, straggler_ratio=math.inf, hang_timeout_s=math.inf))

    def run(case):
        if case not in runs:
            n, m, extra, budget, count, stream = FLOODS[case]
            rng = np.random.default_rng(TEST_SEED)
            R = make_ratings(rng, n=n, m=m,
                             density=0.3 if stream == "pool" else 0.05)
            rows = _flood_rows(R, stream, count, rng)
            cfg = ServerConfig(
                capacity_extra=extra, c_probes=4,
                wal=WalConfig(dir=str(tmp_path_factory.mktemp(case))),
                rotation=RotationConfig(budget_rows=budget), ladder=quiet())
            sync = CFServer(R, ServerConfig(capacity_extra=extra,
                                            c_probes=4, ladder=quiet()))
            inc = CFServer(R, cfg)
            rotated = []
            for r in rows:
                assert sync.onboard_user(r).ok
                res = inc.onboard_user(r)
                assert res.ok
                rotated.append(res.rotated)
            runs[case] = SimpleNamespace(R=R, rows=rows, cfg=cfg, sync=sync,
                                         inc=inc, rotated=rotated)
        return runs[case]

    return run


class TestIncrementalRotation:
    def _flooded(self, rng, *, n=24, m=12, onboards=4):
        """A server whose write region holds ``onboards`` burst rows."""
        R = make_ratings(rng, n=n, m=m)
        srv = CFServer(R, ServerConfig(capacity_extra=8, c_probes=4))
        for i in range(onboards):
            assert srv.onboard_user(R[i]).ok
        return R, srv

    def test_frozen_equals_classic_when_boundary_is_live(self, rng):
        """``rotate_arena`` delegates to ``rotate_arena_frozen`` with
        n_frozen = n_active — same result, explicitly."""
        _, srv = self._flooded(rng)
        a = rotate_arena(srv.state, n_base=srv.n_base, extra=5)
        b = rotate_arena_frozen(srv.state, n_base=srv.n_base,
                                n_frozen=int(srv.state.n_active), extra=5)
        _assert_states_equal(a, b)

    def test_plan_matches_one_shot(self, rng):
        """Chunked precompute + finalize is bit-identical to the one-shot
        frozen rotation, for every chunking."""
        _, srv = self._flooded(rng)
        st = srv.state
        ref = rotate_arena_frozen(st, n_base=srv.n_base,
                                  n_frozen=int(st.n_active), extra=5)
        for chunk in (1, 3, 7, 64):
            plan = RotationPlan(st, n_base=srv.n_base, extra=5,
                                chunk_rows=chunk)
            steps = 0
            while not plan.done:
                assert plan.step(st, 2) > 0
                steps += 1
            if chunk < srv.n_base:
                assert steps > 1                  # genuinely incremental
            _assert_states_equal(plan.finalize(st), ref)

    def test_plan_matches_one_shot_under_mutation(self, rng):
        """Mid-plan mutations — carried onboards past the frozen boundary,
        a refreshed base row (dirty re-merge), a refreshed *burst* row
        (stale block, restart) — all reconcile: finalize is bit-identical
        to the one-shot frozen rotation of the final live state."""
        R, srv = self._flooded(rng)
        n_base = srv.n_base
        plan = RotationPlan(srv.state, n_base=n_base, extra=6, chunk_rows=4)
        n_frozen = plan.n_frozen
        plan.step(srv.state, 8)                   # partial precompute

        # Carried rows: onboards landing after the boundary froze.
        assert srv.onboard_user(R[10]).ok
        assert srv.onboard_user(R[11]).ok
        # Dirty base row: add_rating re-sorts row 2's list.
        assert srv.add_rating(2, 1, 5.0)
        plan.note_write(2)
        plan.step(srv.state, 8)
        # Stale burst block: a frozen burst row is refreshed -> restart.
        assert srv.add_rating(n_base + 1, 2, 3.0)
        plan.note_write(n_base + 1)
        assert plan.restarts == 1

        out = plan.finalize(srv.state)
        ref = rotate_arena_frozen(srv.state, n_base=n_base,
                                  n_frozen=n_frozen, extra=6)
        _assert_states_equal(out, ref)
        # Carried rows kept their write-region position and the arena
        # stayed open: n_active unchanged, new write region appended.
        assert int(out.n_active) == int(srv.state.n_active)
        assert out.capacity == int(srv.state.n_active) + 6

    @pytest.mark.parametrize("case", list(FLOODS))
    def test_incremental_flood_matches_synchronous(self, flood, case):
        """The double-flood oracle, incremental edition: a server rotating
        in budget_rows slices and a synchronously-rotating server end a
        pure onboard flood with bit-identical materialized similarity
        blocks (geometry may differ — content must not), and the write
        region keeps its configured size through the swaps.  At the small
        Douban-like size the flood runs through three swaps or more, none
        forced while the budget finishes a plan within the reserve, and
        each swap marks the onboard it ran in as rotated.  There the two
        servers' write regions differ in width, and TwinSearch computes a
        copied user's cosines to the write region over that width, where
        the CPU backend may round the division in the last bit: between
        two onboarded users the entries agree to 2 ulps, every other entry
        bit for bit."""
        run = flood(case)
        sync, inc = run.sync, run.inc
        assert inc.stats.rotations >= (1 if case == "tiny" else 3)
        assert sum(run.rotated) == inc.stats.rotations
        # the rows carried through each swap take their slots out of the
        # growth: the write region keeps its configured size
        assert inc.k_cap == sync.k_cap == FLOODS[case][2]
        if case == "starved":
            assert inc.stats.forced_drains > 0
        elif case != "tiny":
            assert inc.stats.forced_drains == 0
            assert inc.stats.plan_steps > 0

        def materialized(srv):
            st = rotate_arena(srv.state, n_base=srv.n_base, extra=0)
            n = int(st.n_active)
            return (_unsorted_active(st, n),
                    np.asarray(st.ratings[:n]))
        u_sync, r_sync = materialized(sync)
        u_inc, r_inc = materialized(inc)
        np.testing.assert_array_equal(r_sync, r_inc)
        if case == "tiny":
            np.testing.assert_array_equal(u_sync, u_inc)
            return
        n0 = run.R.shape[0]
        np.testing.assert_array_equal(u_sync[:n0], u_inc[:n0])
        np.testing.assert_array_equal(u_sync[:, :n0], u_inc[:, :n0])
        np.testing.assert_array_max_ulp(u_sync[n0:, n0:], u_inc[n0:, n0:],
                                        maxulp=2)

    @pytest.mark.parametrize("case", ["copy_of_base", "fresh", "starved"])
    def test_incremental_rows_are_exact_lists(self, flood, case):
        """After the swaps every row is the exact list: the rating row as
        sent, then ascending cosines (float64 NumPy reference) to each
        user live when the row was written — every base user for a base
        row, the users before it for a row in the write region — each id
        once, SENTINEL in the other slots."""
        run = flood(case)
        st, n_base = run.inc.state, run.inc.n_base
        n_act = int(st.n_active)
        R_all = np.concatenate([run.R, run.rows], axis=0)
        np.testing.assert_array_equal(np.asarray(st.ratings[:n_act]), R_all)
        vals = np.asarray(st.sim_vals[:n_act])
        idx = np.asarray(st.sim_idx[:n_act])
        width = vals.shape[1]
        for u in range(n_act):
            live = n_base if u < n_base else u
            head = width - live
            v, i = vals[u, head:], idx[u, head:]
            assert (vals[u, :head] <= SENTINEL_GATE).all(), u
            np.testing.assert_array_equal(np.sort(i), np.arange(live))
            assert (np.diff(v) >= 0).all(), u
            want = cosine_vs_all_np(R_all[:live], R_all[u])[i]
            np.testing.assert_allclose(v, want, rtol=0, atol=4e-7,
                                       err_msg=f"row {u}")

    @pytest.mark.parametrize("case", ["copy_of_base", "fresh"])
    def test_incremental_recovery_across_swaps(self, flood, case):
        """``CFServer.recover`` from the WAL alone replays every onboard
        and each ``rotate_commit`` at its point in the stream: the
        recovered arena is the live one, bit for bit."""
        run = flood(case)
        commits = [r for r in run.inc.wal.records()
                   if r.op == "rotate_commit"]
        assert len(commits) == run.inc.stats.rotations >= 3
        recovered = CFServer.recover(run.R, run.cfg)
        _assert_states_equal(recovered.state, run.inc.state)
        assert recovered.n_base == run.inc.n_base
        assert recovered.state.capacity == run.inc.state.capacity

    def test_step_maintenance_drains_between_bursts(self, rng):
        """Quiet-period ticks finish the rotation so no onboard ever pays
        a forced drain."""
        R = make_ratings(rng, n=24, m=12)
        srv = CFServer(R, ServerConfig(
            capacity_extra=6, c_probes=4,
            rotation=RotationConfig(budget_rows=4, reserve_slots=3)))
        for i in range(4):                         # free slots: 6 -> 2
            assert srv.onboard_user(R[i]).ok
        # the plan is in flight now; drain it during the quiet period
        ticks = 0
        while True:
            prog = srv.step_maintenance()
            ticks += 1
            if not prog["active"]:
                break
            assert ticks < 100
        assert srv.stats.rotations == 1
        assert srv.stats.forced_drains == 0
        assert prog["free_slots"] > 2              # swap re-opened the arena
        # the idle-time swap stalled no request: its work is in
        # rotation_ms, not in the pause counters
        assert len(srv.stats.rotation_ms) == 1
        assert len(srv.stats.rotation_pause_ms) == 0
        assert srv.stats.rotation_pause_s == 0.0
        # the next rotation swaps on the request path, and only its pause
        # is recorded, separately from the total rotation work
        i = 4
        while srv.stats.rotations < 2:
            assert srv.onboard_user(R[i % 24]).ok
            i += 1
            assert i < 100
        assert len(srv.stats.rotation_ms) == 2
        assert len(srv.stats.rotation_pause_ms) == 1
        assert srv.stats.summary()["rotation_pause_max_ms"] > 0.0
        assert srv.stats.rotation_pause_s * 1e3 == pytest.approx(
            srv.stats.rotation_pause_ms[0])

    def test_rotation_ms_still_tracks_rotations(self, rng):
        R = make_ratings(rng, n=20, m=10)
        srv = CFServer(R, ServerConfig(
            capacity_extra=4, c_probes=4,
            rotation=RotationConfig(budget_rows=4)))
        for i in range(14):
            assert srv.onboard_user(R[i % 20]).ok
        assert srv.stats.rotations >= 1
        assert len(srv.stats.rotation_ms) == srv.stats.rotations
        assert len(srv.stats.rotation_pause_ms) == srv.stats.rotations


class TestIncrementalRotationCrash:
    """Crash mid-partial-rotation: recovery lands bit-exact at every
    injected point.  The invariants are sharp per point — a pure
    precompute slice logs nothing, a logged-but-unapplied swap replays
    via ``rotate_arena_frozen``, an applied swap recovers as-is."""

    def _config(self, tmp_path, tag):
        return ServerConfig(
            capacity_extra=6, c_probes=4,
            snapshot=SnapshotConfig(every=100, check_every=100,
                                    dir=str(tmp_path / f"{tag}-snap")),
            wal=WalConfig(dir=str(tmp_path / f"{tag}-wal")),
            rotation=RotationConfig(budget_rows=2))

    def _crash_run(self, R, tmp_path, point):
        cfg = self._config(tmp_path, "victim")
        victim = CFServer(R, cfg)
        install_crash(victim, point, nth=1)
        crashed = False
        for i in range(10):
            try:
                victim.onboard_user(R[i])
            except SimulatedCrash as e:
                assert e.point == point
                crashed = True
                break
        assert crashed, f"crash point {point} never fired"
        return cfg, victim

    def test_crash_on_precompute_step_loses_nothing(self, rng, tmp_path):
        """``rotation.step`` logs nothing — recovery must equal the
        victim's live state at the crash, bit for bit."""
        R = make_ratings(rng, n=30, m=12)
        cfg, victim = self._crash_run(R, tmp_path, "rotation.step")
        recovered = CFServer.recover(R, cfg)
        _assert_states_equal(recovered.state, victim.state)
        assert recovered.n_base == victim.n_base

    def test_crash_after_commit_record_replays_the_swap(self, rng,
                                                        tmp_path):
        """``rotation.commit_post_wal``: the swap is logged but not
        applied.  Recovery must replay it — bit-identical to the frozen
        rotation of the victim's (pre-swap) live state."""
        R = make_ratings(rng, n=30, m=12)
        cfg, victim = self._crash_run(R, tmp_path,
                                      "rotation.commit_post_wal")
        plan = victim._plan
        assert plan is not None and plan.done
        expected = rotate_arena_frozen(victim.state, n_base=plan.n_base,
                                       n_frozen=plan.n_frozen,
                                       extra=plan.extra)
        recovered = CFServer.recover(R, cfg)
        _assert_states_equal(recovered.state, expected)
        assert recovered.n_base == plan.n_frozen
        assert recovered.stats.rotations == 1

    def test_crash_after_swap_recovers_the_swap(self, rng, tmp_path):
        """``rotation.post_swap``: swap logged and applied — recovery
        equals the victim's post-swap state."""
        R = make_ratings(rng, n=30, m=12)
        cfg, victim = self._crash_run(R, tmp_path, "rotation.post_swap")
        recovered = CFServer.recover(R, cfg)
        _assert_states_equal(recovered.state, victim.state)
        assert recovered.n_base == victim.n_base
        assert recovered.state.capacity == victim.state.capacity

    @pytest.mark.parametrize("point", ROTATION_CRASH_POINTS)
    def test_recovered_run_converges_with_uncrashed(self, rng, tmp_path,
                                                    point):
        """After recovery, re-issuing the unapplied requests converges to
        the same arena as an uncrashed incremental run."""
        R = make_ratings(rng, n=30, m=12)
        n_ops = 10
        oracle = CFServer(R, self._config(tmp_path, "oracle"))
        for i in range(n_ops):
            assert oracle.onboard_user(R[i]).ok

        cfg, victim = self._crash_run(R, tmp_path, point)
        recovered = CFServer.recover(R, cfg)
        applied = int(recovered.state.n_active) - 30
        for i in range(applied, n_ops):
            assert recovered.onboard_user(R[i]).ok
        _assert_states_equal(recovered.state, oracle.state)
        assert recovered.n_base == oracle.n_base


# ---------------------------------------------------------------------------
# WAL group commit + batched replay — ISSUE 9 tentpole
# ---------------------------------------------------------------------------

class TestWalGroupCommit:
    def _rec(self, i):
        return dict(fields={"i": i},
                    arrays={"x": np.full(4, i, np.float32)})

    def test_batch_coalesces_into_one_sync(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path / "w"))
        with wal.batch():
            for i in range(5):
                wal.append(i + 1, "onboard", **self._rec(i))
            assert wal.syncs == 0            # nothing flushed mid-batch
        assert wal.syncs == 1                # one write+fsync for all 5
        assert [r.seq for r in wal.records()] == [1, 2, 3, 4, 5]
        assert wal.appended == 5 and len(wal) == 5

    def test_unbatched_appends_sync_each(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path / "w"))
        for i in range(5):
            wal.append(i + 1, "onboard", **self._rec(i))
        assert wal.syncs == 5

    def test_batched_records_survive_reopen(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path / "w"))
        with wal.batch():
            for i in range(3):
                wal.append(i + 1, "onboard", **self._rec(i))
        wal.close()
        w2 = WriteAheadLog(str(tmp_path / "w"))
        recs = w2.records()
        assert [r.seq for r in recs] == [1, 2, 3]
        np.testing.assert_array_equal(recs[2].arrays["x"],
                                      np.full(4, 2, np.float32))

    def test_reads_and_truncation_flush_pending(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path / "w"))
        with wal.batch():
            wal.append(1, "onboard", **self._rec(1))
            # a read inside the batch must see the buffered record
            assert [r.seq for r in wal.records()] == [1]
            assert wal.syncs == 1
            wal.append(2, "onboard", **self._rec(2))
            wal.truncate_after(1)            # flushes, then rewrites
            assert len(wal) == 1 and wal.last_seq == 1
        assert [r.seq for r in wal.records()] == [1]

    def test_nested_batches_flush_once_at_outermost(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path / "w"))
        with wal.batch():
            wal.append(1, "onboard", **self._rec(1))
            with wal.batch():
                wal.append(2, "onboard", **self._rec(2))
            assert wal.syncs == 0            # inner exit does not flush
        assert wal.syncs == 1

    def test_onboard_batch_one_fsync_and_bit_exact_recovery(self, rng,
                                                            tmp_path):
        R = make_ratings(rng, n=24, m=12)
        cfg = ServerConfig(capacity_extra=16, c_probes=4,
                           wal=WalConfig(dir=str(tmp_path / "wal")))
        srv = CFServer(R, cfg)
        results = srv.onboard_batch([R[i] for i in range(5)])
        assert all(r.ok for r in results)
        assert srv.wal.syncs == 1            # the whole batch: one fsync
        # recovery over the group-committed log is still bit-exact
        recovered = CFServer.recover(R, cfg)
        _assert_states_equal(recovered.state, srv.state)

    def test_group_commit_off_syncs_per_record(self, rng, tmp_path):
        R = make_ratings(rng, n=24, m=12)
        cfg = ServerConfig(capacity_extra=16, c_probes=4,
                           wal=WalConfig(dir=str(tmp_path / "wal"),
                                         group_commit=False))
        srv = CFServer(R, cfg)
        srv.onboard_batch([R[i] for i in range(5)])
        assert srv.wal.syncs == 5


class TestBatchedReplay:
    def _mutate(self, srv, R, fresh):
        """A mixed op stream: twin + traditional onboards (runs longer
        than the replay chunk), then add_ratings, then more onboards."""
        for i in range(6):
            assert srv.onboard_user(R[i]).ok
        for i in range(3):
            assert srv.onboard_user(fresh[i], use_twinsearch=False).ok
        for u, it, v in ((2, 1, 5.0), (0, 3, 4.0), (25, 2, 3.0),
                         (7, 5, 2.0), (1, 1, 1.0)):
            assert srv.add_rating(u, it, v)
        for i in range(3):
            assert srv.onboard_user(R[10 + i]).ok

    def test_batched_replay_bit_exact_vs_serial_and_live(self, rng,
                                                         tmp_path):
        R = make_ratings(rng, n=24, m=12)
        fresh = make_ratings(np.random.default_rng(55), n=4, m=12)

        def cfg(batch):
            # WAL only (no snapshot dir): recovery replays the full log
            # from a fresh build, and takes no truncating checkpoint, so
            # both recoveries see the same records.
            return ServerConfig(capacity_extra=16, c_probes=4,
                                wal=WalConfig(dir=str(tmp_path / "wal"),
                                              replay_batch=batch))

        live = CFServer(R, cfg(1))
        self._mutate(live, R, fresh)

        serial = CFServer.recover(R, cfg(1))
        batched = CFServer.recover(R, cfg(4))
        assert serial.stats.wal_replayed == batched.stats.wal_replayed == 17
        _assert_states_equal(serial.state, live.state)
        _assert_states_equal(batched.state, live.state)
        assert batched.stats.twin_hits == serial.stats.twin_hits
        assert batched.stats.fallbacks == serial.stats.fallbacks
        assert batched.stats.onboarded == serial.stats.onboarded
        # and both keep serving identically
        assert batched.recommend(3, n=5) == serial.recommend(3, n=5)

    def test_batched_replay_spans_rotation_records(self, rng, tmp_path):
        """Rotations break replay runs; the replayed geometry and state
        still land bit-exact with the live server."""
        R = make_ratings(rng, n=24, m=12)

        def cfg(batch):
            return ServerConfig(capacity_extra=4, c_probes=4,
                                wal=WalConfig(dir=str(tmp_path / "wal"),
                                              replay_batch=batch))

        live = CFServer(R, cfg(1))
        for i in range(11):                  # > capacity_extra: rotates
            assert live.onboard_user(R[i % 20]).ok
        assert live.stats.rotations >= 1

        batched = CFServer.recover(R, cfg(3))
        _assert_states_equal(batched.state, live.state)
        assert batched.n_base == live.n_base
        assert batched.stats.rotations == live.stats.rotations


# ---------------------------------------------------------------------------
# ServerConfig surface (api_redesign satellites)
# ---------------------------------------------------------------------------

class TestServerConfigShim:
    LEGACY = dict(capacity_extra=12, c_probes=5, sim_tol=1e-5,
                  measure="cosine", seed=3, rating_range=(1.0, 5.0),
                  quarantine_capacity=128, latency_window=256,
                  recover_after=16, shed_cooldown_s=0.5,
                  snapshot_every=32, snapshot_keep=2, check_every=4,
                  rotate_headroom=1.5, wal_fsync=False,
                  wal_group_commit=False, wal_replay_batch=8,
                  rotation_budget_rows=3, rotation_reserve_slots=2,
                  drain_on_shed=False)

    def test_kwargs_round_trip(self):
        cfg = ServerConfig.from_kwargs(**self.LEGACY)
        flat = cfg.to_kwargs()
        for key, val in self.LEGACY.items():
            assert flat[key] == val, key
        # and the flat form rebuilds the identical config
        assert ServerConfig.from_kwargs(**flat) == cfg

    def test_kwargs_map_into_sub_configs(self):
        cfg = ServerConfig.from_kwargs(**self.LEGACY)
        assert cfg.capacity_extra == 12
        assert cfg.snapshot.every == 32 and cfg.snapshot.check_every == 4
        assert cfg.wal.fsync is False and cfg.wal.replay_batch == 8
        assert cfg.rotation.headroom == 1.5
        assert cfg.rotation.budget_rows == 3
        assert cfg.rotation.reserve_slots == 2
        assert cfg.ladder.recover_after == 16
        assert cfg.ladder.drain_on_shed is False

    def test_unknown_kwarg_raises_type_error(self):
        with pytest.raises(TypeError, match="unexpected keyword"):
            ServerConfig.from_kwargs(no_such_knob=1)

    def test_legacy_kwargs_warn_and_match_config(self, rng):
        R = make_ratings(rng, n=20, m=10)
        with pytest.warns(DeprecationWarning, match="ServerConfig"):
            old = CFServer(R, capacity_extra=4, c_probes=4, seed=7)
        new = CFServer(R, ServerConfig(capacity_extra=4, c_probes=4,
                                       seed=7))
        _assert_states_equal(old.state, new.state)
        a = old.onboard_user(R[0])
        b = new.onboard_user(R[0])
        assert a.user_id == b.user_id and a.twin_found == b.twin_found

    def test_config_surface_does_not_warn(self, rng):
        R = make_ratings(rng, n=20, m=10)
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error", DeprecationWarning)
            CFServer(R, ServerConfig(capacity_extra=4, c_probes=4))

    def test_config_plus_legacy_kwargs_is_an_error(self, rng):
        R = make_ratings(rng, n=20, m=10)
        with pytest.raises(ValueError, match="not both"):
            CFServer(R, ServerConfig(), capacity_extra=4)

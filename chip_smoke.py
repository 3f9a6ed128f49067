"""Bring-up smoke of the CF recommendation server on one TPU chip.

Drives ``CFServer`` the way ``python -m repro.launch.serve`` does, in this
one process on one chip, at the MovieLens-1M shape: 6,040 users x 3,706
items with 1,000,209 ratings, synthesised from a seed.  The server runs
the default ``ServerConfig`` (64 write slots, 8 probes) with a write-ahead
log and disk snapshots in a temporary directory.  Phases, in order:

  warmup       build the arena, the first onboard and the first reads
  twin_burst   16 copies of one existing user (every copy finds a twin)
  fresh_burst  16 distinct fresh profiles (none finds a twin)
  rotate       onboards until the arena rotates (the list_merge kernel)
  reads        a few add_rating calls, recommend_batch over 256 users,
               predict_batch over 256 (user, item) pairs
  recover      CFServer.recover from the WAL and snapshot directories
  kernels      the read-scoring and rotation-merge programs hold
               compiled Mosaic kernels (``tpu_custom_call``)

Every answer is checked against plain NumPy (``core/reference.py``): twin
decisions given the probes the WAL recorded, similarity rows to 1e-5,
recommendations and predictions to 1e-5, recovery bit-exact.  Each phase
prints its wall time, compile time and peak device memory; the last line
of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU, or when
any check fails, the script exits non-zero and prints no ``ok``.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0
TOL = 1e-5                   # similarity / score agreement with NumPy
N_REC, K_NEIGHBORS = 10, 20
SENTINEL = -2.0              # value of an inactive slot in a sorted list
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclass(frozen=True)
class Scale:
    n_users: int
    n_items: int
    n_ratings: int
    burst: int = 16
    reads: int = 256


ML_1M = Scale(6040, 3706, 1_000_209)


class SmokeFailure(Exception):
    """A phase produced a wrong or refused answer."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu() -> dict:
    """The device the run is for; fails unless JAX's first device is a
    TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"JAX's first device is {dev.platform}, not a TPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


class CompileMeter:
    """Seconds the XLA and Mosaic compilers run, and how many programs they
    compile (a persistent-cache hit compiles nothing)."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.programs += 1


def cosine_rows(R: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(len(rows), n) float64 cosine of R[rows] against every row of R."""
    Rf = R.astype(np.float64)
    norms = np.maximum(np.linalg.norm(Rf, axis=1), 1e-12)
    return (Rf[rows] @ Rf.T) / (norms[rows, None] * norms[None, :])


class Smoke:
    """The phases, run in order against one server."""

    PHASES = ("warmup", "twin_burst", "fresh_burst", "rotate", "reads",
              "recover")

    def __init__(self, scale: Scale, workdir: Path, seed: int = SEED):
        import jax
        from repro.data import synth_ratings
        from repro.serving import ServerConfig, SnapshotConfig, WalConfig

        self.scale = scale
        self.rng = np.random.default_rng(seed)
        self.R = synth_ratings(seed, scale.n_users, scale.n_items,
                               scale.n_ratings)
        self.config = ServerConfig(
            wal=WalConfig(dir=str(workdir / "wal")),
            snapshot=SnapshotConfig(dir=str(workdir / "snapshots")))
        # Fresh profiles for warm-up, the no-twin burst and the onboards
        # up to rotation; as many ratings each as the average user.
        n_fresh = 2 + scale.burst + self.config.capacity_extra
        per_user = scale.n_ratings // scale.n_users
        self.fresh = list(synth_ratings(seed + 1, n_fresh, scale.n_items,
                                        n_fresh * per_user))
        keys = {r.tobytes() for r in self.R}
        check(len({r.tobytes() for r in self.fresh}) == n_fresh
              and not keys & {r.tobytes() for r in self.fresh},
              "fresh profiles must be distinct from each other and the base")
        # Host mirror of the arena's rating rows, by user id.
        self.mirror = np.zeros((scale.n_users + n_fresh + scale.burst,
                                scale.n_items), np.float32)
        self.mirror[:scale.n_users] = self.R
        self.n = scale.n_users
        self.srv = None
        self.results = []
        self.meter = CompileMeter()
        jax.monitoring.register_event_duration_secs_listener(self.meter)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self.meter)

    @property
    def ratings(self) -> np.ndarray:
        return self.mirror[:self.n]

    # -- running and reporting ---------------------------------------------

    def run_phase(self, name: str) -> dict:
        import jax
        c0, p0 = self.meter.seconds, self.meter.programs
        t0 = time.perf_counter()
        info = getattr(self, name)() or {}
        wall = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        line = {"phase": name, "wall_s": wall,
                "compile_s": self.meter.seconds - c0,
                "programs_compiled": self.meter.programs - p0,
                "peak_device_bytes": stats.get("peak_bytes_in_use"), **info}
        print(json.dumps(line), flush=True)
        return line

    # -- onboarding ---------------------------------------------------------

    def onboard(self, row: np.ndarray):
        res = self.srv.onboard_user(row)
        self.results.append(res)
        check(res.ok and res.rung == "twinsearch",
              f"onboard refused or degraded: {res}")
        check(res.user_id == self.n,
              f"user id {res.user_id} is not the next slot")
        self.mirror[self.n] = row
        self.n += 1
        return res

    def check_twins(self, results, expect_twin: bool) -> None:
        """Twin decisions agree with ``twinsearch_np`` given the probes the
        WAL recorded for each onboard, and match the expectation."""
        from repro.core.reference import twinsearch_np
        srv = self.srv
        n_base = srv.n_base
        vals = np.asarray(srv.state.sim_vals[:n_base])
        idx = np.asarray(srv.state.sim_idx[:n_base])
        by_seq = {r.seq: r for r in srv.wal.records()}
        for res in results:
            rec = by_seq[res.seq]
            check(rec.op == "onboard" and rec.fields["use_twin"],
                  f"WAL record {res.seq} is not a twin-search onboard")
            found, _, _ = twinsearch_np(
                self.ratings[:n_base], vals, idx, rec.arrays["ratings"],
                rec.arrays["probes"], srv.tol)
            check(found == res.twin_found == expect_twin,
                  f"user {res.user_id}: twin_found={res.twin_found}, "
                  f"reference {found}, expected {expect_twin}")

    def check_rows(self, users, n_live) -> None:
        """Sorted lists of ``users`` are ascending, hold every user id below
        ``n_live`` (one bound, or one per user) once with its cosine to
        TOL, and SENTINEL elsewhere."""
        users = np.asarray(users)
        bounds = np.broadcast_to(n_live, users.shape)
        vals = np.asarray(self.srv.state.sim_vals[users])
        idx = np.asarray(self.srv.state.sim_idx[users])
        want = cosine_rows(self.ratings, users)
        for u, n_live, v, i, w in zip(users, bounds, vals, idx, want):
            check(bool(np.all(np.diff(v) >= 0)), f"row {u} not ascending")
            live = (i >= 0) & (i < n_live)
            check(np.array_equal(np.sort(i[live]), np.arange(n_live)),
                  f"row {u} does not list users 0..{n_live - 1} once each")
            err = np.abs(v[live] - w[i[live]])
            check(bool(np.all(err <= TOL)),
                  f"row {u}: similarity off by {err.max():.3g}")
            check(bool(np.all(v[~live] == SENTINEL)),
                  f"row {u}: a slot past user {n_live - 1} is live")

    def check_new_rows(self, results) -> None:
        """An onboarded user's row covers exactly the users before it."""
        ids = [res.user_id for res in results]
        self.check_rows(ids, n_live=ids)

    # -- phases -------------------------------------------------------------

    def warmup(self) -> dict:
        import jax
        from repro.serving import CFServer
        t0 = time.perf_counter()
        self.srv = CFServer(self.R, self.config)
        jax.block_until_ready(self.srv.state)
        build_s = time.perf_counter() - t0
        c0 = self.meter.seconds
        res = self.onboard(self.fresh.pop())
        self.check_new_rows([res])
        users = self.rng.integers(0, self.ratings.shape[0], self.scale.reads)
        items = self.rng.integers(0, self.scale.n_items, self.scale.reads)
        self.srv.recommend_batch(users, n=N_REC, k_neighbors=K_NEIGHBORS)
        self.srv.predict_batch(users, items, k=K_NEIGHBORS)
        return {"build_s": build_s,
                "first_onboard_and_read_compile_s": self.meter.seconds - c0}

    def twin_burst(self) -> dict:
        from repro.data import plant_twins
        source = int(self.rng.integers(0, self.scale.n_users))
        burst = plant_twins(self.R, self.scale.burst, source_user=source)
        res = [self.onboard(r) for r in burst]
        self.check_twins(res, expect_twin=True)
        self.check_new_rows(res)
        return {"source_user": source, "twins": sum(r.twin_found
                                                    for r in res),
                **onboard_ms(res)}

    def fresh_burst(self) -> dict:
        res = [self.onboard(self.fresh.pop())
               for _ in range(self.scale.burst)]
        self.check_twins(res, expect_twin=False)
        self.check_new_rows(res)
        return {"twins": sum(r.twin_found for r in res), **onboard_ms(res)}

    def rotate(self) -> dict:
        res = []
        while self.fresh and not (res and res[-1].rotated):
            res.append(self.onboard(self.fresh.pop()))
        check(bool(res) and res[-1].rotated, "the arena never rotated")
        # The rotation merged the whole write region into the base lists:
        # every row older than the triggering onboard now holds all of
        # them.  Check a sample of base rows, the merged burst rows and
        # the first row written after the swap.
        n_frozen = res[-1].user_id
        base = self.rng.choice(self.scale.n_users, 32, replace=False)
        self.check_rows(np.concatenate([base, np.arange(self.scale.n_users,
                                                        n_frozen)]),
                        n_live=n_frozen)
        self.check_new_rows(res[-1:])
        return {"onboards": len(res), "n_frozen": n_frozen, **onboard_ms(res),
                "rotation_s": self.srv.stats.rotation_ms[-1] / 1e3,
                "capacity": self.srv.state.capacity}

    def reads(self) -> dict:
        srv, n = self.srv, self.n
        for _ in range(4):
            u = int(self.rng.integers(0, n))
            i = int(self.rng.integers(0, self.scale.n_items))
            r = float(self.rng.integers(1, 6))
            check(srv.add_rating(u, i, r), f"add_rating({u}, {i}) refused")
            self.ratings[u, i] = r
        users = self.rng.integers(0, n, self.scale.reads)
        items = self.rng.integers(0, self.scale.n_items, self.scale.reads)
        times = {}
        for rep in ("first", "again"):         # the first call compiles
            t0 = time.perf_counter()
            recs = srv.recommend_batch(users, n=N_REC,
                                       k_neighbors=K_NEIGHBORS)
            times[f"recommend_batch_{rep}_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            preds = srv.predict_batch(users, items, k=K_NEIGHBORS)
            times[f"predict_batch_{rep}_s"] = time.perf_counter() - t0

        # Host kNN over the server's own sorted lists: the same neighbour
        # choice, then scores and predictions in float64.
        vals = np.asarray(srv.state.sim_vals[users])
        idx = np.asarray(srv.state.sim_idx[users])
        for b, u in enumerate(users):
            sims, nbrs = top_k_np(vals[b], idx[b], int(u), n, K_NEIGHBORS)
            r = self.ratings[nbrs].astype(np.float64)
            w = np.maximum(sims, 0.0)[:, None]
            score = (w * r).sum(0) / np.maximum((w * (r != 0)).sum(0),
                                                1e-12)
            score[self.ratings[u] != 0] = -np.inf
            got_items = np.asarray([it for it, _ in recs[b]])
            got = np.asarray([s for _, s in recs[b]])
            nth = np.sort(score)[::-1][N_REC - 1]
            check(len(recs[b]) == N_REC
                  and np.allclose(got, score[got_items], atol=TOL, rtol=0)
                  and got[-1] >= nth - TOL,
                  f"recommend({u}) disagrees with the host kNN")
            ri = r[:, items[b]]
            wp = np.where((ri != 0) & (sims > 0), sims, 0.0)
            want = (wp * ri).sum() / wp.sum() if wp.sum() > 0 else 0.0
            check(abs(preds[b] - want) <= TOL,
                  f"predict({u}, {items[b]}) = {preds[b]}, host {want}")
        return {**times, "add_ratings": 4}

    def recover(self) -> dict:
        from repro.serving import CFServer
        live = self.srv
        st = live.stats
        check(st.errors == 0 and st.rollbacks == 0
              and not live.quarantine.records,
              f"errors={st.errors} rollbacks={st.rollbacks} "
              f"quarantined={len(live.quarantine.records)}")
        check(all(r.status != "rolled_back" for r in self.results),
              "an onboard was rolled back")
        back = CFServer.recover(self.R, self.config)
        for name in ("sim_vals", "sim_idx", "ratings", "n_active"):
            a = np.asarray(getattr(live.state, name))
            b = np.asarray(getattr(back.state, name))
            check(a.shape == b.shape and a.tobytes() == b.tobytes(),
                  f"recovered {name} differs from the live server's")
        return {"replayed": back.stats.wal_replayed}

    def kernels(self) -> dict:
        """The read-scoring program and the rotation merge lower to
        compiled Mosaic kernels on this backend."""
        import jax.numpy as jnp
        from repro.core.rotation import _merge_base_rows
        from repro.kernels.knn_score.ops import knn_recommend_topn
        st = self.srv.state
        B, k = self.scale.reads, K_NEIGHBORS
        read = knn_recommend_topn.lower(
            st.ratings, jnp.ones((B, k), jnp.float32),
            jnp.zeros((B, k), jnp.int32), jnp.zeros((B,), jnp.int32),
            N_REC).compile().as_text()
        n_base, k_cap = self.srv.n_base, self.srv.k_cap
        N = st.capacity
        merge = _merge_base_rows.lower(
            st.sim_vals, st.sim_idx, jnp.zeros((k_cap, N), jnp.float32),
            jnp.arange(n_base, dtype=jnp.int32),
            jnp.arange(n_base, N, dtype=jnp.int32), n_base=n_base,
            use_pallas=None).compile().as_text()
        found = {"read_scoring": "tpu_custom_call" in read,
                 "rotation_merge": "tpu_custom_call" in merge}
        check(all(found.values()), f"interpreted kernels: {found}")
        return {"tpu_custom_call": found}


def onboard_ms(results) -> dict:
    """Median and worst server-side onboard latency (compiles included)."""
    ms = [r.latency_ms for r in results]
    return {"onboard_ms_p50": float(np.median(ms)),
            "onboard_ms_max": float(np.max(ms))}


def top_k_np(vals, idx, user, n_active, k):
    """``core.knn.top_k_neighbors`` on the host: the k largest live,
    non-self entries of a sorted list (earlier position wins a tie)."""
    ranked = np.where((idx != user) & (idx < n_active) & (vals > -1.5),
                      vals, SENTINEL)
    pos = np.argsort(-ranked, kind="stable")[:k]
    sims = ranked[pos].astype(np.float64)
    return sims, np.where(sims > -1.5, idx[pos], 0)


def main() -> int:
    try:
        device = require_tpu()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "device": device}), flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        smoke = Smoke(ML_1M, Path(tmp))
        try:
            for name in Smoke.PHASES + ("kernels",):
                smoke.run_phase(name)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
        finally:
            smoke.close()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

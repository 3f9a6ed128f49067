"""Neighbourhood-CF recommendation server with the paper's TwinSearch
new-user onboarding fast path, hardened for bursty production traffic.

Request surface (what a real deployment fronts with an RPC layer):

  * ``onboard_user(ratings)``   — TwinSearch -> copy, or traditional build
                                  fallback; returns a typed
                                  ``OnboardResult`` (legacy
                                  ``(uid, info)`` unpacking still works).
  * ``onboard_batch(batch)``    — a sequence of onboards under one WAL
                                  group commit (one fsync per batch).
  * ``recommend(user, n)``      — top-n unseen items via kNN scores.
  * ``predict(user, item)``     — kNN weighted-average rating.
  * ``recommend_batch(users)``  — B recommendations in one device
                                  dispatch: per-row guard validation
                                  (a bad row is quarantined, the rest are
                                  served), twin-query dedup before
                                  dispatch, one host transfer of results.
  * ``predict_batch(users, items)`` — B predictions, same contract.
  * ``add_rating(user, item, r)``— incremental (Papagelis-style) update of
                                  the affected similarity row.
  * ``step_maintenance()``      — drain a slice of any pending incremental
                                  rotation during quiet periods.

Configuration is a frozen ``serving.ServerConfig`` (sub-configs:
``SnapshotConfig`` / ``WalConfig`` / ``RotationConfig`` / ``LadderConfig``);
the historical flat kwargs still work via a deprecation shim.

With ``RotationConfig.budget_rows > 0`` arena rotation is *incremental*:
a ``RotationPlan`` starts when free write slots fall to ``reserve_slots``
and merges at most ``budget_rows`` base rows per onboard/tick (plus retry
backoff waits and shed backpressure windows; span ``cf.rotation.step``,
``ServerStats.plan_steps`` / ``plan_step_s``), while new users keep
landing in the buffer past the frozen boundary; the final atomic swap
(span ``cf.rotation.swap``) is bit-identical to the synchronous rotation
of the live state and is the stall one request pays
(``ServerStats.rotation_pause_s`` / ``rotation_pause_ms``).
The swap is WAL-logged as ``rotate_commit`` (frozen boundary + growth),
so recovery replays it deterministically via ``rotate_arena_frozen``.

Resilience contract: **no public entrypoint raises to the caller.**

  * Malformed payloads (NaN/Inf, wrong shape/dtype, out-of-range, bogus
    ids) are refused by ``serving/guard.py`` before touching any jitted
    kernel and land in a bounded quarantine; the caller gets a structured
    refusal (``status="rejected"``).
  * Capacity exhaustion triggers **arena rotation**
    (``core/rotation.py``): the write region compacts into a larger base
    arena via PR 1's fused k-way merge — onboarding continues past the
    original ``capacity_extra`` indefinitely.  ``rotate_headroom`` scales
    the fresh write region with the absorbed burst (hysteresis against
    back-to-back synchronous rotations); each rotation's duration lands
    in ``ServerStats.rotation_ms``.
  * Onboard latencies feed a ``StragglerMonitor`` (``training/elastic.py``)
    driving a **degradation ladder**: twinsearch -> traditional ->
    degraded-replica -> shed-with-backpressure.  Latency verdicts walk
    twinsearch -> traditional -> shed directly; the ``degraded`` rung is
    entered when replication redundancy drops (a replica died) and pins
    the server at the traditional path until background re-replication
    restores r-way redundancy.  Every transition is counted in
    ``ServerStats``.
  * The jitted onboard call runs under retry-with-exponential-backoff and
    a deadline (transient executor faults); a call that still fails is
    quarantined, not raised (and its write-ahead record is aborted).

Durability contract: **a crash or a shard loss never forces a similarity
recompute.**

  * Every mutating op is appended to a **write-ahead log**
    (``serving/wal.py``, ``wal_dir``/``wal_fsync`` knobs) *before* it is
    applied; on restart ``CFServer.recover(...)`` replays the log on top
    of the newest durable checkpoint, reproducing the pre-crash arena
    bit-exactly.  The log truncates at each durable snapshot and rewinds
    on rollback, so it always holds exactly the ops since the state the
    next recovery would start from.
  * With ``replication=ReplicationConfig(...)`` the arena's row shards
    are mirrored r-way (``distributed/replication.py``).  A poisoned
    primary row — bit-flip, lost shard — is *healed* from a surviving
    replica (pure data movement) instead of rolled back; a lost replica
    is rebuilt from survivors incrementally between requests.  Rollback
    to the last good snapshot remains the backstop when no replica
    survives.
  * Periodic atomic **snapshots** (in-memory always; on disk via
    ``training/checkpoint.py`` when ``snapshot_dir`` is set, now with
    per-leaf CRC32 verification and fall-back-to-previous-step on
    corruption) pair with a cheap NaN/ordering invariant check
    (``kernels/verify_rows``) every ``check_every`` onboards.

Query contract: **reads are never refused.**  The batch endpoints
validate per row — a malformed row is quarantined and its slot answers
empty/0.0 while the rest of the batch is served — and the degradation
ladder's shed rung *degrades* queries (``k_neighbors`` drops by
``SHED_QUERY_K_DIV``) instead of shedding them: a read is cheaper than
the refusal dance.  Before dispatch, **twin-query dedup**
(``serving/dedup.py``) collapses rows whose scoring inputs — top-k
neighbour sims + ids and, for recommendations, the user's own rating
row — are bitwise identical: the paper's twins share similarity lists,
so they provably share recommendation scores, and only the unique rows
are scored (``ServerStats.queries`` / ``query_unique``).  Unique-row and
batch shapes are bucketed to powers of two so the jitted query programs
are compile-once per bucket, and each batch pays exactly two host transfers
(the probe for dedup keys, the fanned-out results).

State is the fixed-capacity ``CFState`` (jit-friendly); all mutating ops
are jitted once per arena shape and reused.  ``stats`` tracks twin hits /
fallbacks / latencies / resilience transitions — the serving-side
visibility the benchmarks read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import time
import warnings
from collections import deque
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (CFState, build_state, knn, set0_cap)
from repro.core import baseline as base_lib
from repro.core import twinsearch as ts
from repro.core import update as upd_lib
from repro.core.rotation import (RotationPlan, rotate_arena,
                                 rotate_arena_frozen)
from repro.distributed.replication import ReplicatedArena, ReplicationConfig
from repro.kernels.knn_score.ops import knn_recommend_topn
from repro.kernels.verify_rows.ops import arena_healthy
from repro.serving import guard
from repro.serving.dedup import dedup_rows
from repro.serving.config import ServerConfig
from repro.serving.tracing import span
from repro.serving.wal import WriteAheadLog
from repro.training import checkpoint
from repro.training.elastic import Action, StragglerMonitor

log = logging.getLogger(__name__)

# Degradation ladder levels (ascending = more degraded).
LEVEL_TWINSEARCH = 0
LEVEL_TRADITIONAL = 1
LEVEL_DEGRADED = 2          # replica redundancy lost; rebuilding in background
LEVEL_SHED = 3
LEVEL_NAMES = {LEVEL_TWINSEARCH: "twinsearch",
               LEVEL_TRADITIONAL: "traditional",
               LEVEL_DEGRADED: "degraded",
               LEVEL_SHED: "shed"}

# Shed-rung query degradation: reads are served with k_neighbors // this
# (floor 1) instead of being refused — the ladder's read-path analogue of
# the twinsearch -> traditional write-path fallback.
SHED_QUERY_K_DIV = 4


def _bucket_pow2(n: int) -> int:
    """Smallest power of two >= n — the jit-cache shape bucket for the
    variable-size query batches (bounded recompiles, fixed shapes)."""
    return 1 << max(0, n - 1).bit_length()


@dataclass
class ServerStats:
    onboarded: int = 0
    twin_hits: int = 0
    fallbacks: int = 0
    overflows: int = 0
    rejected: int = 0
    shed: int = 0
    retries: int = 0
    errors: int = 0
    rotations: int = 0
    snapshots: int = 0
    rollbacks: int = 0
    repairs: int = 0            # poisoned rows healed from replicas
    degradations: int = 0
    recoveries: int = 0
    wal_appends: int = 0
    wal_replayed: int = 0
    plan_restarts: int = 0      # incremental-rotation precompute restarts
    forced_drains: int = 0      # buffer filled before the plan finished
    plan_steps: int = 0         # incremental-rotation slices run
    plan_step_s: float = 0.0    # seconds in those slices, device included
    rotation_pause_s: float = 0.0   # seconds requests stalled for rotations
    queries: int = 0            # query rows served (valid rows only)
    query_batches: int = 0      # recommend_batch / predict_batch calls
    query_unique: int = 0       # rows actually scored after twin dedup
    query_degraded: int = 0     # rows served at shed-reduced k_neighbors
    latency_window: int = 1024
    onboard_ms: deque = field(init=False)
    rotation_ms: deque = field(init=False)
    rotation_pause_ms: deque = field(init=False)
    query_ms: deque = field(init=False)

    def __post_init__(self) -> None:
        # Fixed-size ring buffers: sustained traffic must not grow host
        # memory; summary() percentiles are over the trailing window.
        self.onboard_ms = deque(maxlen=self.latency_window)
        # Each rotation's work.  Synchronous: the merge and the new arena's
        # assembly, all of it inside the onboard that found the arena full
        # (the re-wrap of the programs excluded).  Incremental: the plan's
        # slices and its swap-time assembly summed, spread over the
        # onboards the slices ran in, so not a stall.
        self.rotation_ms = deque(maxlen=64)
        # What rotation actually cost a *single request*: the synchronous
        # stall (full rotation, or just the final swap when incremental;
        # a forced drain's remaining slices count in it), the same seconds
        # ``rotation_pause_s`` sums.
        self.rotation_pause_ms = deque(maxlen=64)
        # Per-batch query latency (the trailing-window view).
        self.query_ms = deque(maxlen=self.latency_window)

    def summary(self) -> dict:
        ms = sorted(self.onboard_ms) or [0.0]
        rot = sorted(self.rotation_ms) or [0.0]
        qms = sorted(self.query_ms) or [0.0]
        return {
            "onboarded": self.onboarded,
            "twin_hits": self.twin_hits,
            "fallbacks": self.fallbacks,
            "overflows": self.overflows,
            "rejected": self.rejected,
            "shed": self.shed,
            "retries": self.retries,
            "errors": self.errors,
            "rotations": self.rotations,
            "snapshots": self.snapshots,
            "rollbacks": self.rollbacks,
            "repairs": self.repairs,
            "degradations": self.degradations,
            "recoveries": self.recoveries,
            "wal_appends": self.wal_appends,
            "wal_replayed": self.wal_replayed,
            "plan_restarts": self.plan_restarts,
            "forced_drains": self.forced_drains,
            "plan_steps": self.plan_steps,
            "plan_step_s": self.plan_step_s,
            "rotation_pause_s": self.rotation_pause_s,
            "onboard_p50_ms": ms[len(ms) // 2],
            "onboard_p99_ms": ms[min(len(ms) - 1, int(len(ms) * 0.99))],
            "rotation_p50_ms": rot[len(rot) // 2],
            "rotation_max_ms": rot[-1],
            "rotation_pause_max_ms": max(self.rotation_pause_ms, default=0.0),
            "queries": self.queries,
            "query_batches": self.query_batches,
            "query_unique": self.query_unique,
            "query_degraded": self.query_degraded,
            "query_p50_ms": qms[len(qms) // 2],
            "query_p99_ms": qms[min(len(qms) - 1, int(len(qms) * 0.99))],
            "query_dedup_savings": (1.0 - self.query_unique
                                    / max(self.queries, 1)),
        }


# Legacy dict-key -> OnboardResult attribute (identity for the rest).
_RESULT_KEY_MAP = {"ms": "latency_ms", "level": "rung"}


@dataclass(frozen=True)
class OnboardResult:
    """Typed outcome of ``onboard_user`` / ``onboard_batch``.

    Replaces the historical ``(user_id, info_dict)`` tuple.  For migration
    the old shapes still work: iterating yields ``(user_id, result)`` so
    ``uid, info = srv.onboard_user(r)`` unpacks as before, and
    ``result["ms"]`` / ``result["level"]`` / ``result.get(...)`` resolve
    through the legacy key names (``ms`` -> ``latency_ms``, ``level`` ->
    ``rung``).
    """
    user_id: int = -1
    status: str = "ok"        # ok|rejected|shed|error|rolled_back
    rung: str = "twinsearch"  # ladder level the request was served at
    latency_ms: float = 0.0
    rotated: bool = False     # a rotation (or its swap) ran in this call
    seq: int = -1             # WAL sequence number (-1: nothing logged)
    twin_found: bool = False
    reason: str | None = None
    detail: str | None = None
    retry_after_s: float | None = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    # -- legacy (user_id, info_dict) compatibility --------------------------

    def __iter__(self):
        yield self.user_id
        yield self

    def __getitem__(self, key):
        if isinstance(key, int):
            return (self.user_id, self)[key]
        try:
            return getattr(self, _RESULT_KEY_MAP.get(key, key))
        except AttributeError:
            raise KeyError(key) from None

    def get(self, key, default=None):
        try:
            val = self[key]
        except KeyError:
            return default
        return default if val is None else val

    def __contains__(self, key) -> bool:
        try:
            return self[key] is not None
        except KeyError:
            return False


class CFServer:
    def __init__(self, ratings: np.ndarray,
                 config: ServerConfig | None = None, *,
                 recover: bool = False, **legacy):
        """``CFServer(ratings, config=ServerConfig(...))`` is the surface;
        the historical flat kwargs (``capacity_extra=..., wal_dir=...``)
        still work through a shim that round-trips them into a
        ``ServerConfig`` and emits a ``DeprecationWarning``."""
        if config is not None and legacy:
            raise ValueError(
                "pass either config=ServerConfig(...) or the legacy flat "
                f"kwargs, not both (got legacy keys {sorted(legacy)})")
        if config is None:
            if legacy:
                warnings.warn(
                    "CFServer's flat keyword arguments are deprecated; "
                    "pass config=ServerConfig(...) (see "
                    "repro.serving.config, ServerConfig.from_kwargs maps "
                    "the old names)", DeprecationWarning, stacklevel=2)
            config = ServerConfig.from_kwargs(**legacy)
        self.config = config
        self._rcfg = config.rotation
        self._wcfg = config.wal
        self._lcfg = config.ladder

        self.n_base = int(ratings.shape[0])
        self.k_cap = int(config.capacity_extra)
        self.c = config.c_probes
        self.tol = config.sim_tol
        self.rating_range = (float(config.rating_range[0]),
                             float(config.rating_range[1]))
        self.rotate_headroom = float(config.rotation.headroom)

        def build_arena(R):
            return build_state(R, capacity_extra=config.capacity_extra,
                               measure=config.measure)

        self.state: CFState = jax.jit(build_arena)(
            jnp.asarray(ratings, jnp.float32))
        self._key = jax.random.PRNGKey(config.seed)
        self.stats = ServerStats(latency_window=config.latency_window)
        self.quarantine = guard.Quarantine(
            capacity=config.quarantine_capacity)

        # Degradation ladder + retry machinery.  The monitor's clock is the
        # server's time source for shed cooldowns too, so fault-injection
        # tests drive the whole ladder in virtual time.  Retry backoff
        # waits double as maintenance ticks: time spent blocked on a
        # transient fault drains the rotation plan instead of idling.
        self.retry = config.ladder.retry or guard.RetryPolicy()
        if self.retry.on_wait is None:
            self.retry = dataclasses.replace(
                self.retry, on_wait=self._drain_during_wait)
        self.monitor = config.ladder.monitor or StragglerMonitor(
            window=64, straggler_ratio=4.0, hang_timeout_s=30.0,
            consecutive_to_shrink=3)
        self._clock = self.monitor.clock
        self.level = LEVEL_TWINSEARCH
        self.recover_after = int(config.ladder.recover_after)
        self.shed_cooldown_s = float(config.ladder.shed_cooldown_s)
        self._healthy_streak = 0
        self._shed_until = 0.0

        # Snapshot / rollback machinery.
        self.snapshot_every = int(config.snapshot.every)
        self.snapshot_dir = config.snapshot.dir
        self.snapshot_keep = int(config.snapshot.keep)
        self.check_every = int(config.snapshot.check_every)
        self._since_snapshot = 0
        self._since_check = 0

        # Incremental rotation: a pending chunked plan (None = no rotation
        # in flight; always None when rotation.budget_rows == 0).
        self._plan: RotationPlan | None = None

        # Durability machinery.  ``_seq`` is the monotonic mutation counter:
        # it numbers WAL records AND disk checkpoints, so "checkpoint at S
        # plus WAL records with seq > S" is always the current state.
        self._seq = 0
        self.wal = (WriteAheadLog(config.wal.dir, fsync=config.wal.fsync)
                    if config.wal.dir is not None else None)
        self._replaying = False
        self._crash_hook = None        # test seam: see testing/faults.py
        self.replicas: ReplicatedArena | None = None

        # All jitted entrypoints are constructed eagerly (construction is
        # free — tracing happens on first call) so a first-call exception
        # can never leave the server half-initialised; the update cache is
        # still *computed* lazily (it is O(N^2) memory).
        self._cache = None
        self._build_jits()

        if recover:
            self._recover_state()

        if config.replication is not None:
            self.replicas = ReplicatedArena(self.state, config.replication)

        self._snapshot = None
        self._take_snapshot()            # the construction-time good state

    @classmethod
    def recover(cls, ratings: np.ndarray,
                config: ServerConfig | None = None,
                **kwargs) -> "CFServer":
        """Rebuild a server after a crash: restore the newest durable
        checkpoint under the snapshot dir (falling back past corrupt
        steps), then replay the WAL suffix through the same jitted ops —
        the recovered arena is bit-identical to the pre-crash one, with
        zero similarity recompute.  Pass the same construction config as
        the original server."""
        return cls(ratings, config, recover=True, **kwargs)

    # -- internal machinery -------------------------------------------------

    def _build_jits(self) -> None:
        """(Re)wrap the jitted ops for the *current* arena geometry.
        Called at construction and after every rotation/rollback/restore —
        the closures capture ``n_base``/``s_max``/``k_cap``, which those
        transitions change.  Each program is a named function, so the
        device trace names it (``jit_onboard_twinsearch``, ...)."""
        self.s_max = set0_cap(self.n_base)
        n_base, k_cap = self.n_base, self.k_cap

        def onboard_twinsearch(st, r0, probes):
            return ts.onboard_twinsearch(st, r0, probes, s_max=self.s_max,
                                         n_base=n_base, k_cap=k_cap,
                                         tol=self.tol)

        self._onboard = jax.jit(onboard_twinsearch)
        self._onboard_trad = jax.jit(base_lib.onboard_traditional)
        self._recommend = jax.jit(knn.recommend,
                                  static_argnames=("k_neighbors", "n_rec"))
        self._predict = jax.jit(knn.predict, static_argnames=("k",))

        # Batched query path.  The probe returns everything the host needs
        # to build twin-dedup keys in ONE transfer (top-k sims + neighbour
        # ids + the users' own rating rows); the score call then runs only
        # the deduped rows through the fused scoring kernel and cuts top-n
        # on device, so results come back in one more transfer.  k / n_rec
        # are static; batch shapes are pow2-bucketed by the endpoints.
        def probe_recommend(st, users, k):
            return (*knn.top_k_neighbors_batch(st, users, k),
                    st.ratings[users])

        def score_recommend(st, sims, nbrs, users, n_rec):
            return knn_recommend_topn(st.ratings, jnp.maximum(sims, 0.0),
                                      nbrs, users, n_rec)

        self._probe_rec = jax.jit(probe_recommend, static_argnames=("k",))
        self._probe_topk = jax.jit(knn.top_k_neighbors_batch,
                                   static_argnames=("k",))
        self._score_rec = jax.jit(score_recommend, static_argnames=("n_rec",))
        self._score_pred = jax.jit(
            jax.vmap(knn.predict_from_neighbors, in_axes=(None, 0, 0, 0)))
        self._init_cache = jax.jit(upd_lib.init_cache)
        self._add = jax.jit(upd_lib.add_rating)
        self._healthy = arena_healthy

        # Batched WAL replay: one jitted dispatch per chunk of B records
        # instead of one per record — a lax.scan over the *same* per-step
        # ops the serial path runs, so the replayed state stays
        # bit-identical; only dispatch overhead is amortised.  Twin and
        # traditional records get separate specialised scans: replay
        # compiles exactly the paths the log exercises (a mixed cond body
        # would pay both compiles even for a pure-twin log).  Chunk size
        # is baked into the traced shapes; runs shorter than B fall back
        # to the per-record path.
        s_max, tol = self.s_max, self.tol

        def _twin_chunk(st, Rb, Pb):
            def body(s, inp):
                r0, probes = inp
                s2, res = ts.onboard_twinsearch(
                    s, r0, probes, s_max=s_max, n_base=n_base,
                    k_cap=k_cap, tol=tol)
                return s2, (jnp.asarray(res.found, jnp.bool_),
                            jnp.asarray(res.overflowed, jnp.bool_))

            st, (founds, overs) = jax.lax.scan(body, st, (Rb, Pb))
            return st, founds, overs

        self._replay_twin_chunk = jax.jit(_twin_chunk)

        def _trad_chunk(st, Rb):
            def body(s, r0):
                return base_lib.onboard_traditional(s, r0), None

            st, _ = jax.lax.scan(body, st, Rb)
            return st

        self._replay_trad_chunk = jax.jit(_trad_chunk)

        def _chunk_add(st, cache, users, items, vals):
            def body(carry, inp):
                s, c = carry
                u, i, v = inp
                s, c = upd_lib.add_rating(s, c, u, i, v)
                return (s, c), None

            (st, cache), _ = jax.lax.scan(body, (st, cache),
                                          (users, items, vals))
            return st, cache

        self._replay_add_chunk = jax.jit(_chunk_add)
        # key_{i+1} = split(key_i)[0], n times in one dispatch — the same
        # chain the live path walks one split per twin-search onboard
        def advance_key(key, m):
            return jax.lax.fori_loop(
                0, m, lambda _, k: jax.random.split(k)[0], key)

        self._advance_key = jax.jit(advance_key)

    def _reject(self, kind: str, reason: str, payload=None,
                detail: str = "") -> dict:
        self.stats.rejected += 1
        self.quarantine.record(kind, reason, payload, detail)
        return {"status": "rejected", "reason": reason}

    def _crashpoint(self, name: str) -> None:
        """Deterministic crash injection seam (``testing/faults.py``
        installs the hook); a no-op in production."""
        if self._crash_hook is not None:
            self._crash_hook(name)

    # -- degradation ladder -------------------------------------------------

    def _replicas_degraded(self) -> bool:
        return self.replicas is not None and self.replicas.degraded()

    def _set_level(self, level: int) -> None:
        if level == self.level:
            return
        if level > self.level:
            self.stats.degradations += 1
            log.warning("degrading %s -> %s", LEVEL_NAMES[self.level],
                        LEVEL_NAMES[level])
        else:
            self.stats.recoveries += 1
            log.info("recovering %s -> %s", LEVEL_NAMES[self.level],
                     LEVEL_NAMES[level])
        self.level = level
        self._healthy_streak = 0
        if level == LEVEL_SHED:
            self._shed_until = self._clock() + self.shed_cooldown_s

    def _step_down(self) -> None:
        """One recovery step down the ladder.  The ``degraded`` rung is
        owned by replication: stepping out of SHED lands on it while
        redundancy is still lost, and the rung itself is pinned until
        re-replication completes (``_replication_tick`` releases it)."""
        if self.level == LEVEL_SHED:
            self._set_level(LEVEL_DEGRADED if self._replicas_degraded()
                            else LEVEL_TRADITIONAL)
        elif self.level == LEVEL_DEGRADED:
            if not self._replicas_degraded():
                self._set_level(LEVEL_TRADITIONAL)
        else:
            self._set_level(max(LEVEL_TWINSEARCH, self.level - 1))

    def _apply_monitor(self, action: Action) -> None:
        if action is Action.ABORT:
            # A hang-scale latency: shed immediately, don't walk the ladder.
            self._set_level(LEVEL_SHED)
        elif action is Action.CHECKPOINT_AND_SHRINK:
            # Latency verdicts walk twinsearch -> traditional -> shed; the
            # degraded rung is entered only by replica-loss events.
            self._set_level(LEVEL_TRADITIONAL
                            if self.level == LEVEL_TWINSEARCH
                            else LEVEL_SHED)
        else:
            self._healthy_streak += 1
            if (self.level > LEVEL_TWINSEARCH
                    and self._healthy_streak >= self.recover_after):
                self._step_down()

    def _replication_tick(self) -> None:
        """Per-request background replication work: advance re-replication
        by the configured row budget and keep the ladder's ``degraded``
        rung in sync with actual redundancy."""
        if self.replicas is None:
            return
        self.replicas.step_rebuild()
        if self.replicas.degraded():
            if self.level < LEVEL_DEGRADED:
                self._set_level(LEVEL_DEGRADED)
        elif self.level == LEVEL_DEGRADED:
            self._set_level(LEVEL_TRADITIONAL)

    # -- rotation -----------------------------------------------------------

    def _rotate(self) -> None:
        """Grow the arena: compact the write region into a new base (see
        ``core/rotation.py``) and retarget every jitted op at the new
        geometry.  The incremental-update cache keys on the old shapes and
        is dropped; replicas re-mirror the new geometry."""
        old_capacity = self.state.capacity
        t0 = time.perf_counter()
        self.state = rotate_arena(self.state, n_base=self.n_base,
                                  extra=self.k_cap,
                                  headroom=self.rotate_headroom)
        self.state.sim_vals.block_until_ready()
        dt_ms = (time.perf_counter() - t0) * 1e3
        self.n_base = int(self.state.n_active)
        self.k_cap = self.state.capacity - self.n_base
        self._cache = None
        self._build_jits()
        self.stats.rotations += 1
        self.stats.rotation_ms.append(dt_ms)
        if self.replicas is not None:
            self.replicas.reset(self.state)
        log.info("arena rotated: capacity %d -> %d (n_base=%d, %.1fms)",
                 old_capacity, self.state.capacity, self.n_base, dt_ms)

    # -- incremental rotation (rotation.budget_rows > 0) --------------------

    def _free_slots(self) -> int:
        return self.state.capacity - int(self.state.n_active)

    def _reserve_slots(self) -> int:
        r = self._rcfg.reserve_slots
        return int(r) if r is not None else max(1, self.k_cap // 4)

    def _start_plan(self) -> None:
        k0 = int(self.state.n_active) - self.n_base
        extra = max(self.k_cap,
                    int(math.ceil(self.rotate_headroom * self.k_cap)))
        self._plan = RotationPlan(self.state, n_base=self.n_base,
                                  extra=extra,
                                  chunk_rows=max(1, self._rcfg.budget_rows))
        log.info("incremental rotation started: n_base=%d burst=%d "
                 "extra=%d", self.n_base, k0, extra)

    def _maintenance_tick(self, budget_rows: int | None = None, *,
                          stall: bool = True) -> bool:
        """Advance background rotation by one bounded slice and swap when
        the plan completes; True iff it swapped.  Called at safe points
        only — between mutating ops, never inside one (the in-flight op's
        closures captured the pre-swap state).  ``stall``: a request waits
        for this tick, so a swap counts as its rotation pause."""
        if self._rcfg.budget_rows <= 0:
            return False
        if self._plan is None:
            if self.k_cap <= 0 or self._free_slots() > self._reserve_slots():
                return False
            self._start_plan()
        budget = (int(budget_rows) if budget_rows is not None
                  else self._rcfg.budget_rows)
        if not self._plan.done:
            self._plan_step(budget)
            self._crashpoint("rotation.step")
        if self._plan.done:
            self._swap_rotation(stall=stall)
            return True
        return False

    def _plan_step(self, budget_rows: int) -> None:
        """One slice of the pending plan, counted in ``plan_steps`` and
        ``plan_step_s``."""
        t0 = time.perf_counter()
        with span("cf.rotation.step"):
            self._plan.step(self.state, budget_rows)
        self.stats.plan_steps += 1
        self.stats.plan_step_s += time.perf_counter() - t0

    def _drain_during_wait(self, delay_s: float) -> None:
        """Retry-backoff hook: spend otherwise-idle wait time on rotation
        *chunks*.  Never swaps — a retry is mid-onboard and the pending
        ``run`` closure captured the pre-swap state."""
        if (self._plan is not None and not self._plan.done
                and self._rcfg.budget_rows > 0):
            self._plan_step(self._rcfg.budget_rows)

    def _force_drain(self) -> None:
        """The buffer filled before the plan finished (or before it even
        started): finish the rotation now, synchronously.  Degrades to
        exactly the old stall in the worst case — never worse."""
        t0 = time.perf_counter()
        if self._plan is None:
            self._start_plan()
        else:
            self.stats.forced_drains += 1
        while not self._plan.done:
            self._plan.step(self.state, max(1, self.n_base))
        self._swap_rotation(drained_s=time.perf_counter() - t0)

    def _note_pause(self, seconds: float) -> None:
        """A rotation's stall on the request path."""
        self.stats.rotation_pause_s += seconds
        self.stats.rotation_pause_ms.append(seconds * 1e3)

    def _swap_rotation(self, drained_s: float = 0.0,
                       stall: bool = True) -> None:
        """The atomic swap: log ``rotate_commit``, finalize the plan from
        the live state (bit-identical to ``rotate_arena_frozen``), and
        retarget geometry.  The WAL record carries the frozen boundary and
        the growth so recovery replays the swap deterministically at the
        same point in the op stream.  ``drained_s``: seconds a forced drain
        already stalled this request for; ``stall`` false (an idle-time
        swap) leaves the pause counters alone."""
        plan = self._plan
        old_capacity = self.state.capacity
        # The rows carried past the frozen boundary take their slots out of
        # the growth, so the new write region keeps the plan's size.
        carried = int(self.state.n_active) - plan.n_frozen
        plan.extra = max(1, plan.extra - carried)
        t0 = time.perf_counter()
        with span("cf.rotation.swap"):
            self._log("rotate_commit", fields={"n_base": plan.n_base,
                                               "n_frozen": plan.n_frozen,
                                               "extra": plan.extra})
            self._crashpoint("rotation.commit_post_wal")
            new_state = plan.finalize(self.state)
            new_state.sim_vals.block_until_ready()
            self._install_rotated(new_state, n_base=plan.n_frozen)
        pause_s = drained_s + time.perf_counter() - t0
        self._plan = None
        self.stats.rotations += 1
        self.stats.rotation_ms.append(plan.elapsed_ms)
        if stall:
            self._note_pause(pause_s)
        self.stats.plan_restarts += plan.restarts
        self._crashpoint("rotation.post_swap")
        log.info("arena rotated (incremental): capacity %d -> %d "
                 "(n_base=%d, %.1fms total, %.1fms pause)", old_capacity,
                 self.state.capacity, self.n_base, plan.elapsed_ms,
                 pause_s * 1e3)

    def _install_rotated(self, new_state: CFState, *, n_base: int) -> None:
        """Point the server at a rotated arena (live swap or WAL replay)."""
        self.state = new_state
        self.n_base = int(n_base)
        self.k_cap = self.state.capacity - self.n_base
        self._cache = None
        self._build_jits()
        if self.replicas is not None:
            self.replicas.reset(self.state)

    def step_maintenance(self, budget_rows: int | None = None) -> dict:
        """Public maintenance tick: drain up to ``budget_rows`` rows of any
        pending incremental rotation (defaults to the configured
        per-onboard budget).  Wire this into idle-period hooks — e.g. the
        ladder's ``StragglerMonitor`` quiet windows — so rotations finish
        between bursts instead of inside them.  A swap made here stalls no
        request, so the pause counters leave it out."""
        self._maintenance_tick(budget_rows, stall=False)
        plan = self._plan
        return {"active": plan is not None,
                "remaining_rows": plan.remaining_rows if plan else 0,
                "free_slots": self._free_slots()}

    # -- durability: WAL / snapshot / rollback / recovery -------------------

    def _log(self, op: str, fields: dict | None = None,
             arrays: dict | None = None) -> int:
        """Assign the next mutation sequence number and (when a WAL is
        attached and we are not replaying) append the record *before* the
        op is applied — the write-ahead contract."""
        self._seq += 1
        if self.wal is not None and not self._replaying:
            with span("cf.wal.append"):
                self.wal.append(self._seq, op, fields, arrays)
            self.stats.wal_appends += 1
        return self._seq

    def _take_snapshot(self) -> None:
        self._snapshot = (self.state, self.n_base, self._key, self._seq)
        self.stats.snapshots += 1
        self._since_snapshot = 0
        if self.snapshot_dir is not None:
            checkpoint.save(self.snapshot_dir, self._seq, self.state,
                            extra={"n_base": self.n_base,
                                   "key": np.asarray(self._key).tolist(),
                                   "wal_seq": self._seq},
                            keep_last=self.snapshot_keep)
            if self.wal is not None:
                # The checkpoint subsumes every logged op; drop them.  The
                # incremental dots cache is re-seeded at this boundary so a
                # replayed timeline (which must init it from the restored
                # ratings) stays bit-identical to the live one.
                self.wal.truncate_through(self._seq)
                self._cache = None

    def _rollback(self) -> None:
        state, n_base, key, seq = self._snapshot
        geometry_changed = (state.capacity != self.state.capacity
                            or n_base != self.n_base)
        self.state, self.n_base, self._key = state, n_base, key
        self.k_cap = state.capacity - n_base
        self._seq = seq
        self._cache = None
        self._plan = None          # precomputed against the discarded state
        if geometry_changed:
            self._build_jits()
        if self.wal is not None:
            self.wal.truncate_after(seq)
        if self.replicas is not None:
            self.replicas.reset(self.state)
        self.stats.rollbacks += 1
        self._since_check = 0
        self._since_snapshot = 0
        log.error("arena invariant violated; rolled back to last good "
                  "snapshot (n_active=%d)", int(state.n_active))

    def _recover_state(self) -> None:
        """Restore the newest loadable checkpoint, then replay the WAL
        suffix.  Zero similarity math: the checkpoint is a byte copy and
        replay re-runs only the logged (cheap) maintenance ops."""
        restored = False
        fell_back = False
        if self.snapshot_dir is not None:
            try:
                tree, step, extra = checkpoint.restore(self.snapshot_dir,
                                                       self.state)
            except FileNotFoundError:
                pass
            else:
                self.state = tree
                self.n_base = int(extra.get("n_base", self.n_base))
                self.k_cap = self.state.capacity - self.n_base
                if "key" in extra:
                    self._key = jnp.asarray(extra["key"], jnp.uint32)
                self._seq = int(extra.get("wal_seq", step))
                self._cache = None
                self._build_jits()
                restored = True
                newest = checkpoint.latest_step(self.snapshot_dir)
                fell_back = newest is not None and newest > step
                log.info("restored checkpoint step %d (n_active=%d)",
                         step, int(self.state.n_active))
        if self.wal is not None:
            # Gap checks run on the WAL's *raw* sequence bounds — aborted
            # ops and their compensation records count (records() filters
            # them out of the replay stream, but their seqs were consumed):
            # an aborted prefix is not a missing prefix, and replaying over
            # a genuinely missing one would silently drop committed ops.
            first_raw = self.wal.first_seq
            if not restored:
                if first_raw > 1:
                    raise RuntimeError(
                        f"WAL starts at seq {first_raw} but no checkpoint "
                        f"could be restored — earlier ops were truncated "
                        f"into a checkpoint that is now missing or corrupt")
            elif (first_raw > self._seq + 1
                    or (fell_back and first_raw == 0)):
                # The newest checkpoint was corrupt and the WAL was already
                # truncated through it: the ops between the fallback step
                # and the corrupt one are unrecoverable.  (A crash between
                # checkpoint.save and the WAL truncation leaves the suffix
                # intact — first_seq <= wal_seq + 1 — and recovers fine.)
                raise RuntimeError(
                    f"restored checkpoint is at seq {self._seq} but the WAL "
                    f"{'is empty' if first_raw == 0 else f'starts at seq {first_raw}'}"
                    f" — ops since seq {self._seq} were truncated into a "
                    f"newer checkpoint that is corrupt; refusing to replay "
                    f"over the gap")
            self._replay(self.wal.records(after_seq=self._seq))
            # Resume numbering past the raw WAL tail: an aborted tail op's
            # seq (and its abort record's) never replays, but reissuing it
            # would make records() drop the next committed op as aborted on
            # a later recovery.
            self._seq = max(self._seq, self.wal.last_seq)

    def _replay(self, records) -> None:
        """Replay a WAL suffix.  With ``wal.replay_batch > 1`` maximal
        contiguous runs of same-op, same-path ``onboard``/``add_rating``
        records are driven through one specialised jitted scan per full
        chunk (same per-step ops — bit-identical state, one dispatch
        instead of B); short runs and run tails take the per-record path.
        ``rotate`` / ``rotate_commit`` records break runs: they change
        arena geometry."""
        records = list(records)
        B = max(1, int(self._wcfg.replay_batch))
        self._replaying = True
        try:
            i = 0
            while i < len(records):
                rec = records[i]
                if B > 1 and rec.op in ("onboard", "add_rating"):
                    j = i
                    while j < len(records) and records[j].op == rec.op:
                        j += 1
                    run = records[i:j]
                    if rec.op == "onboard":
                        self._replay_onboard_run(run, B)
                    else:
                        self._replay_add_rating_run(run, B)
                    i = j
                    continue
                self._seq = rec.seq
                if rec.op == "rotate":
                    self._rotate()
                elif rec.op == "rotate_commit":
                    self._replay_rotate_commit(rec)
                elif rec.op == "onboard":
                    self._replay_onboard(rec)
                elif rec.op == "add_rating":
                    self._replay_add_rating(rec)
                else:
                    log.warning("unknown WAL op %r at seq %d skipped",
                                rec.op, rec.seq)
                self.stats.wal_replayed += 1
                i += 1
        finally:
            self._replaying = False

    def _replay_rotate_commit(self, rec) -> None:
        """Deterministic replay of an incremental rotation's atomic swap:
        the record pins the frozen boundary and growth, so
        ``rotate_arena_frozen`` reproduces the swapped arena bit-exactly
        at the same point in the op stream."""
        f = rec.fields
        new_state = rotate_arena_frozen(
            self.state, n_base=int(f["n_base"]),
            n_frozen=int(f["n_frozen"]), extra=int(f["extra"]))
        new_state.sim_vals.block_until_ready()
        self._install_rotated(new_state, n_base=int(f["n_frozen"]))
        self.stats.rotations += 1

    def _replay_onboard_run(self, run, B: int) -> None:
        # maximal same-path sub-runs, so each chunk hits one specialised jit
        j = 0
        while j < len(run):
            tw = bool(run[j].fields.get("use_twin", False))
            k = j + 1
            while (k < len(run)
                   and bool(run[k].fields.get("use_twin", False)) == tw):
                k += 1
            self._replay_uniform_run(run[j:k], B, use_twin=tw)
            j = k

    def _replay_uniform_run(self, run, B: int, *, use_twin: bool) -> None:
        i = 0
        if use_twin and any(r.arrays["probes"].shape != (self.c,)
                            for r in run):
            i = len(run)             # foreign probe shape: replay serially
        while len(run) - i >= B:
            chunk = run[i:i + B]
            Rb = jnp.asarray(np.stack([r.arrays["ratings"]
                                       .astype(np.float32) for r in chunk]))
            if use_twin:
                Pb = jnp.asarray(np.stack([r.arrays["probes"]
                                           for r in chunk]).astype(np.int32))
                # Advance the PRNG stream exactly as the live path did:
                # one split per twin-search op (probes still come from
                # the records — they are authoritative).
                self._key = self._advance_key(self._key, B)
                st, founds, overs = self._replay_twin_chunk(
                    self.state, Rb, Pb)
                n_found = int(np.asarray(founds).sum())
                self.stats.twin_hits += n_found
                self.stats.fallbacks += B - n_found
                self.stats.overflows += int(np.asarray(overs).sum())
            else:
                st = self._replay_trad_chunk(self.state, Rb)
                self.stats.fallbacks += B
            st.n_active.block_until_ready()
            self.state = st
            self.stats.onboarded += B
            self.stats.wal_replayed += B
            self._seq = chunk[-1].seq
            i += B
        for r in run[i:]:
            self._seq = r.seq
            self._replay_onboard(r)
            self.stats.wal_replayed += 1

    def _replay_add_rating_run(self, run, B: int) -> None:
        i = 0
        if len(run) >= B and self._cache is None:
            # The serial path seeds the cache lazily on the first add;
            # seed it from the same ratings here so the scan sees an
            # identical carry.
            self._cache = self._init_cache(self.state.ratings)
        while len(run) - i >= B:
            chunk = run[i:i + B]
            users = np.asarray([int(r.fields["user"]) for r in chunk],
                               np.int32)
            items = np.asarray([int(r.fields["item"]) for r in chunk],
                               np.int32)
            vals = np.asarray([float(r.fields["rating"]) for r in chunk],
                              np.float32)
            st, cache = self._replay_add_chunk(
                self.state, self._cache, jnp.asarray(users),
                jnp.asarray(items), jnp.asarray(vals))
            st.n_active.block_until_ready()
            self.state, self._cache = st, cache
            self.stats.wal_replayed += B
            self._seq = chunk[-1].seq
            i += B
        for r in run[i:]:
            self._seq = r.seq
            self._replay_add_rating(r)
            self.stats.wal_replayed += 1

    def _replay_onboard(self, rec) -> None:
        r0 = jnp.asarray(rec.arrays["ratings"].astype(np.float32))
        use_twin = bool(rec.fields.get("use_twin", False))
        if use_twin:
            # Advance the PRNG stream exactly as the live path did; the
            # recorded probes equal the re-derived ones, but the record is
            # authoritative (recovery works even from a foreign key state).
            self._key, _ = jax.random.split(self._key)
            probes = jnp.asarray(rec.arrays["probes"])
            new_state, res = self._onboard(self.state, r0, probes)
            found, overflowed = bool(res.found), bool(res.overflowed)
        else:
            new_state = self._onboard_trad(self.state, r0)
            found = overflowed = False
        new_state.n_active.block_until_ready()
        self._commit_onboard(new_state, found, overflowed)

    def _replay_add_rating(self, rec) -> None:
        f = rec.fields
        self._apply_add_rating(int(f["user"]), int(f["item"]),
                               float(f["rating"]))

    # -- health check + snapshot cadence ------------------------------------

    def _is_healthy(self, st: CFState) -> bool:
        """The arena invariant sweep over ``st``, synced to the host."""
        with span("cf.health_check"):
            return bool(self._healthy(st.sim_vals, st.ratings, st.norms,
                                      st.n_active))

    def _state_ok(self) -> bool:
        """Verify the arena invariant; heal poisoned rows from replicas
        (exact, similarity-free) when possible, roll back to the last good
        snapshot otherwise.  False iff a rollback happened."""
        if self._is_healthy(self.state):
            return True
        if self.replicas is not None:
            fixed, rows = self.replicas.repair(self.state)
            if fixed is not None and self._is_healthy(fixed):
                self.state = fixed
                self._cache = None
                self.stats.repairs += 1
                log.warning("healed %d poisoned arena rows from replicas",
                            len(rows))
                return True
        self._rollback()
        return False

    def _check_and_snapshot(self) -> bool:
        """Periodic poison detection + snapshot cadence.  Returns False if
        the current state failed the invariant and was rolled back (a
        replica-healed state counts as healthy)."""
        self._since_check += 1
        self._since_snapshot += 1
        if self._since_check >= self.check_every:
            self._since_check = 0
            if self.replicas is not None:
                self.replicas.sweep()
            if not self._state_ok():
                return False
        if self._since_snapshot >= self.snapshot_every:
            # Never snapshot unverified state: a snapshot of a poisoned
            # arena would poison every future rollback.
            if self._is_healthy(self.state):
                self._take_snapshot()
        return True

    # -- onboarding ---------------------------------------------------------

    def _commit_onboard(self, new_state: CFState, found: bool,
                        overflowed: bool) -> None:
        self.state = new_state
        self.stats.onboarded += 1
        self.stats.twin_hits += found
        self.stats.fallbacks += not found
        self.stats.overflows += overflowed
        if self.replicas is not None:
            self.replicas.apply_rows([int(new_state.n_active) - 1],
                                     new_state)

    def onboard_user(self, ratings: np.ndarray, *,
                     use_twinsearch: bool = True) -> OnboardResult:
        reason = guard.validate_ratings_vector(
            ratings, n_items=self.state.n_items,
            rating_range=self.rating_range)
        if reason is not None:
            self._reject("onboard", reason, ratings)
            return OnboardResult(status="rejected", reason=reason,
                                 rung=LEVEL_NAMES[self.level])

        self._replication_tick()
        if self.level == LEVEL_SHED:
            if self._clock() < self._shed_until:
                self.stats.shed += 1
                # Backpressure time is free maintenance time.
                swapped = (self._lcfg.drain_on_shed
                           and self._maintenance_tick())
                return OnboardResult(
                    status="shed", rung=LEVEL_NAMES[self.level],
                    rotated=swapped,
                    retry_after_s=self._shed_until - self._clock())
            # Cooldown expired: probe the cheaper build path again.
            self._set_level(LEVEL_DEGRADED if self._replicas_degraded()
                            else LEVEL_TRADITIONAL)

        # Background rotation tick: a safe point (no op in flight).
        rotated = self._maintenance_tick()

        self._crashpoint("onboard.pre_wal")
        if int(self.state.n_active) >= self.state.capacity:
            rotated = True
            with span("cf.onboard.rotate"):
                if self._rcfg.budget_rows > 0:
                    # The plan didn't finish (or start) in time: drain it.
                    self._force_drain()
                else:
                    t0 = time.perf_counter()
                    self._log("rotate")
                    self._crashpoint("rotate.post_wal")
                    self._rotate()
                    self._note_pause(time.perf_counter() - t0)

        r0_np = np.asarray(ratings, dtype=np.float32)
        r0 = jnp.asarray(r0_np)
        use_twin = use_twinsearch and self.level == LEVEL_TWINSEARCH
        if use_twin:
            self._key, sub = jax.random.split(self._key)
            probes = jax.random.randint(sub, (self.c,), 0, self.n_base)

            def run():
                new_state, res = self._onboard(self.state, r0, probes)
                new_state.n_active.block_until_ready()
                return new_state, bool(res.found), bool(res.overflowed)
        else:
            probes = None

            def run():
                new_state = self._onboard_trad(self.state, r0)
                new_state.n_active.block_until_ready()
                return new_state, False, False

        seq = self._log(
            "onboard", fields={"use_twin": bool(use_twin)},
            arrays={"ratings": r0_np,
                    "probes": (np.asarray(probes) if probes is not None
                               else np.empty((0,), np.int32))})
        self._crashpoint("onboard.post_wal")

        self.monitor.step_started()
        t0 = time.perf_counter()
        try:
            with span("cf.onboard.run"):
                (new_state, found, overflowed), retries = \
                    guard.call_with_retry(run, self.retry)
        except Exception as e:          # noqa: BLE001 — contract: no raise
            self.monitor.step_finished()
            self.stats.errors += 1
            # Compensate the write-ahead record: the op never applied, so
            # replay must skip it.
            self._log("abort", fields={"target": seq})
            self.quarantine.record("onboard", guard.R_ERROR, ratings,
                                   detail=repr(e))
            log.error("onboard failed after retries: %r", e)
            return OnboardResult(status="error", reason=guard.R_ERROR,
                                 rung=LEVEL_NAMES[self.level],
                                 rotated=rotated, seq=seq, detail=repr(e))
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._apply_monitor(self.monitor.step_finished())

        self.stats.retries += retries
        self._commit_onboard(new_state, found, overflowed)
        self.stats.onboard_ms.append(dt_ms)
        self._crashpoint("onboard.post_commit")

        if not self._check_and_snapshot():
            return OnboardResult(status="rolled_back", latency_ms=dt_ms,
                                 rung=LEVEL_NAMES[self.level],
                                 rotated=rotated, seq=seq)
        uid = int(self.state.n_active) - 1
        return OnboardResult(user_id=uid, status="ok", twin_found=found,
                             latency_ms=dt_ms, rung=LEVEL_NAMES[self.level],
                             rotated=rotated, seq=seq)

    def onboard_batch(self, ratings_batch, *,
                      use_twinsearch: bool = True) -> list[OnboardResult]:
        """Onboard a sequence of users under one WAL group commit: the
        batch's appends coalesce into a single write+fsync
        (``wal.group_commit``), trading per-record durability for
        per-batch durability — a crash mid-batch replays to the last
        *flushed* batch boundary, never to a torn prefix.  Results are
        per-user ``OnboardResult``s, same contract as ``onboard_user``."""
        ctx = (self.wal.batch()
               if self.wal is not None and self._wcfg.group_commit
               else contextlib.nullcontext())
        with ctx:
            return [self.onboard_user(r, use_twinsearch=use_twinsearch)
                    for r in ratings_batch]

    # -- queries ------------------------------------------------------------

    def _query_k(self, k_neighbors: int) -> int:
        """Degradation-ladder interaction for reads: the shed rung serves
        queries at a reduced neighbour count instead of refusing them."""
        if self.level == LEVEL_SHED:
            return max(1, int(k_neighbors) // SHED_QUERY_K_DIV)
        return int(k_neighbors)

    def _pre_query(self) -> None:
        if self.replicas is not None:
            # Failover read: heal any poisoned rows from replicas before
            # answering, so a lost shard degrades durability, not answers.
            self._replication_tick()
            self._state_ok()

    def _note_query_batch(self, n_valid: int, n_unique: int, dt_ms: float,
                          degraded: bool) -> None:
        self.stats.query_batches += 1
        self.stats.queries += n_valid
        self.stats.query_unique += n_unique
        self.stats.query_ms.append(dt_ms)
        if degraded:
            self.stats.query_degraded += n_valid

    @staticmethod
    def _pad_bucket(arr: np.ndarray) -> np.ndarray:
        """Pad axis 0 to the pow2 bucket by repeating the last row — a
        valid, already-requested row, so the padded program computes
        nothing undefined and the host slices the extras away."""
        n = arr.shape[0]
        pad = _bucket_pow2(n) - n
        if pad == 0:
            return arr
        return np.concatenate([arr, np.repeat(arr[-1:], pad, axis=0)])

    def recommend_batch(self, users, n: int = 10, k_neighbors: int = 20
                        ) -> list[list[tuple[int, float]]]:
        """Top-``n`` recommendations for a batch of users in one device
        dispatch.  Per-row guard: an invalid user id is quarantined and
        its slot answers ``[]`` while the rest of the batch is served.
        Twin dedup: rows whose (top-k sims, neighbour ids, own-ratings)
        keys are bitwise identical are scored once and fanned out."""
        users = list(users)
        results: list[list[tuple[int, float]]] = [[] for _ in users]
        with span("cf.read.validate"):
            valid = [i for i, u in enumerate(users)
                     if not (guard.validate_user_id(
                         u, int(self.state.n_active))
                         and self._reject("recommend", guard.R_USER_ID, u))]
        if not valid:
            return results
        self._pre_query()
        k_eff = self._query_k(k_neighbors)
        t0 = time.perf_counter()

        with span("cf.read.probe"):
            uvec = np.asarray([int(users[i]) for i in valid], np.int32)
            sims, nbrs, rows = jax.device_get(self._probe_rec(
                self.state, jnp.asarray(self._pad_bucket(uvec)), k_eff))
        B = len(uvec)
        sims, nbrs, rows = sims[:B], nbrs[:B], rows[:B]

        # Twin dedup (probe -> exact verify): the scoring kernel is a
        # deterministic function of exactly (sims, nbrs, own row), so
        # bitwise-equal keys provably share scores.
        with span("cf.read.dedup"):
            keys = np.concatenate([sims.view(np.uint32),
                                   nbrs.view(np.uint32),
                                   rows.view(np.uint32)], axis=1)
            plan = dedup_rows(keys)
            sel = self._pad_bucket(plan.unique_rows)
        with span("cf.read.score"):
            scores, items = jax.device_get(self._score_rec(
                self.state, jnp.asarray(sims[sel]), jnp.asarray(nbrs[sel]),
                jnp.asarray(uvec[sel]), n))

        dt_ms = (time.perf_counter() - t0) * 1e3
        with span("cf.read.fanout"):
            for pos, i in enumerate(valid):
                u = int(plan.scatter[pos])       # fan_out, zipped on host
                results[i] = [(int(it), float(s))
                              for s, it in zip(scores[u], items[u])]
        self._note_query_batch(B, plan.n_unique, dt_ms,
                               degraded=k_eff != int(k_neighbors))
        return results

    def predict_batch(self, users, items, k: int = 20) -> list[float]:
        """kNN rating predictions for B (user, item) pairs in one device
        dispatch; invalid rows are quarantined and answer 0.0.  Twin
        dedup keys on (top-k sims, neighbour ids, item)."""
        users, items = list(users), list(items)
        assert len(users) == len(items), (len(users), len(items))
        results = [0.0] * len(users)
        valid = []
        with span("cf.read.validate"):
            for i, (u, it) in enumerate(zip(users, items)):
                if guard.validate_user_id(u, int(self.state.n_active)):
                    self._reject("predict", guard.R_USER_ID, u)
                elif guard.validate_item_id(it, self.state.n_items):
                    self._reject("predict", guard.R_ITEM_ID, it)
                else:
                    valid.append(i)
        if not valid:
            return results
        self._pre_query()
        k_eff = self._query_k(k)
        t0 = time.perf_counter()

        with span("cf.read.probe"):
            uvec = np.asarray([int(users[i]) for i in valid], np.int32)
            ivec = np.asarray([int(items[i]) for i in valid], np.int32)
            sims, nbrs = jax.device_get(self._probe_topk(
                self.state, jnp.asarray(self._pad_bucket(uvec)), k_eff))
        B = len(uvec)
        sims, nbrs = sims[:B], nbrs[:B]

        with span("cf.read.dedup"):
            keys = np.concatenate([sims.view(np.uint32),
                                   nbrs.view(np.uint32),
                                   ivec.reshape(-1, 1).view(np.uint32)],
                                  axis=1)
            plan = dedup_rows(keys)
            sel = self._pad_bucket(plan.unique_rows)
        with span("cf.read.score"):
            preds = jax.device_get(self._score_pred(
                self.state, jnp.asarray(sims[sel]), jnp.asarray(nbrs[sel]),
                jnp.asarray(ivec[sel])))

        dt_ms = (time.perf_counter() - t0) * 1e3
        with span("cf.read.fanout"):
            for pos, i in enumerate(valid):
                results[i] = float(preds[int(plan.scatter[pos])])
        self._note_query_batch(B, plan.n_unique, dt_ms,
                               degraded=k_eff != int(k))
        return results

    def recommend(self, user: int, n: int = 10,
                  k_neighbors: int = 20) -> list[tuple[int, float]]:
        """Thin B=1 wrapper over ``recommend_batch`` (one device
        dispatch, one host transfer — no per-element sync)."""
        return self.recommend_batch([user], n=n, k_neighbors=k_neighbors)[0]

    def predict(self, user: int, item: int, k: int = 20) -> float:
        """Thin B=1 wrapper over ``predict_batch``."""
        return self.predict_batch([user], [item], k=k)[0]

    # -- maintenance --------------------------------------------------------

    def _apply_add_rating(self, user: int, item: int,
                          rating: float) -> None:
        with span("cf.add_rating.apply"):
            if self._cache is None:
                self._cache = self._init_cache(self.state.ratings)
            self.state, self._cache = self._add(
                self.state, self._cache, jnp.int32(user), jnp.int32(item),
                jnp.float32(rating))
        if self.replicas is not None:
            self.replicas.apply_rows([user], self.state)
        if self._plan is not None:
            # A refreshed row may invalidate part of the rotation plan's
            # precompute; the plan re-merges it before the swap.
            self._plan.note_write(int(user))

    def add_rating(self, user: int, item: int, rating: float) -> bool:
        """Returns True iff the update was applied (False = quarantined)."""
        if guard.validate_user_id(user, int(self.state.n_active)):
            self._reject("add_rating", guard.R_USER_ID, user)
            return False
        if guard.validate_item_id(item, self.state.n_items):
            self._reject("add_rating", guard.R_ITEM_ID, item)
            return False
        reason = guard.validate_rating_value(rating, self.rating_range)
        if reason is not None:
            self._reject("add_rating", reason, rating)
            return False
        self._replication_tick()
        self._crashpoint("add_rating.pre_wal")
        self._log("add_rating", fields={"user": int(user), "item": int(item),
                                        "rating": float(rating)})
        self._crashpoint("add_rating.post_wal")
        self._apply_add_rating(int(user), int(item), float(rating))
        self._crashpoint("add_rating.post_commit")
        return True

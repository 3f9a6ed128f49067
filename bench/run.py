"""One run of one benchmark cell on the chip.

    python3 -m bench.run --workload douban8k.twin_burst_12 --seed 7 \\
        --seconds 20 --trace 0

The run makes the deployment and its traffic from ``--seed``, builds the
server, warms up every program the window will run, drives the open-loop
window, checks what the window produced against the plain reference
(``bench/reference.py``) and, where the deployment keeps checkpoints on
disk, against what a restarted server recovers from them and its log,
and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, with ``--trace 1``, ``breakdown``; the last key,
``checks``, holds each compared number beside its limit, which also end
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

Set-up is everything from process start to the window's first due
request: JAX's start, the data, the server's build, warm-up requests
through the server's own API, one of every operation and batch shape the
window sends (onboards enough to reach the health sweep once), and a
``sync`` of what earlier writes left dirty.  Every program lands in JAX's
persistent cache at ``<checkout>/.jax_cache``, so only a checkout's first
run compiles; an onboarding cell's first run also fills that cache, in a
child process, with the programs of every arena shape its rotations
reach.  A rotation inside the window re-wraps the server's programs at
the new arena shape; the first onboard after it traces them again and
loads them from that cache, as a running server would, and
``compile_s.onboard`` counts that time.

``--rate`` replaces the mix's arrival rate, for a sweep to the knee; the
cells run at the rate their mix files state.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field, replace  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

from bench import datagen, reference, spec, traffic  # noqa: E402
from bench.traffic import ADD_RATING, ONBOARD, PREDICT, READS, RECOMMEND  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
SPANS = {ONBOARD: "onboard_user", RECOMMEND: "recommend_batch",
         PREDICT: "predict_batch", ADD_RATING: "add_rating"}
GRACE_S = 60.0            # the window stops issuing this long after its end
SAMPLE = {"base_rows": 32, "new_rows": 64, "twin_checks": 64,
          "read_requests": 16, "refreshed_rows": 64}
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


NO_CHIP_EXIT = 2


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def configure_jax(cache_dir: Path) -> None:
    """Persistent compile cache at a fixed path, every program kept
    however fast it compiled.  JAX opens its cache once per process; the
    reset makes a second run in one process (the tests) use its own."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts as compiling: the backend event spans the load), the
    programs compiled, and the persistent-cache hits."""

    def __init__(self):
        self.seconds = {e.rsplit("/", 1)[1]: 0.0 for e in COMPILE_EVENTS}
        self.programs = 0
        self.cache_hits = 0
        self.compiled: list[str] = []

    def on_duration(self, event: str, duration: float, fun_name: str = "",
                    **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds[event.rsplit("/", 1)[1]] += duration
            if event.endswith("backend_compile_duration"):
                self.programs += 1
                self.compiled.append(fun_name)

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"seconds": dict(self.seconds), "programs": self.programs,
                "cache_hits": self.cache_hits,
                "compiled": list(self.compiled)}

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self.on_duration)
        monitoring.unregister_event_listener(self.on_event)


# ---------------------------------------------------------------------------
# The deployment
# ---------------------------------------------------------------------------

def server_config(cfg: dict, workdir: Path):
    from repro.serving import (RotationConfig, ServerConfig, SnapshotConfig,
                               WalConfig)
    s = dict(cfg["server"])
    snap, wal, rot = s.pop("snapshot"), s.pop("wal"), s.pop("rotation")
    s["rating_range"] = tuple(s["rating_range"])
    return ServerConfig(
        **s,
        snapshot=SnapshotConfig(
            every=snap["every"], keep=snap["keep"],
            check_every=snap["check_every"],
            dir=str(workdir / "snapshots") if snap["disk"] else None),
        wal=WalConfig(dir=str(workdir / "wal") if wal["enabled"] else None,
                      fsync=wal["fsync"], group_commit=wal["group_commit"],
                      replay_batch=wal["replay_batch"]),
        rotation=RotationConfig(headroom=rot["headroom"],
                                budget_rows=rot["budget_rows"]))


def fill_mark(cache_dir: Path, cell, seconds: float) -> Path:
    """The mark of a filled cache: the shapes follow from the cell's
    deployment and mix and the window's length."""
    return (cache_dir / "bench_marks"
            / f"{cell.name}-{seconds!r}-{_digest(cell)}")


def fill_cache(root: Path, workload: str, seed: int, seconds: float,
               cache_dir: Path, require_chip: bool,
               rate: float | None = None) -> None:
    """The first run of an onboarding cell in a checkout drives a
    throwaway server through the run's onboards, so that the programs of
    every arena shape its rotations reach are compiled into the persistent
    cache during set-up and not inside the window; it then leaves a mark
    in the cache, and later runs skip this.  Every seed reaches the same
    shapes: the onboard count is the same for every seed.  Its calls are
    slow while they compile, so its server's straggler monitor is held
    off: three slow calls in a row would walk the ladder to another path
    and leave the window's programs uncompiled.  It runs in a process of
    its own (``_fill_in_child``), so that the run's own process starts as
    every later run's does, with nothing traced."""
    cell = spec.load(root, workload, rate)
    device_info(cell.workload["chips"], require_chip)
    configure_jax(cache_dir)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.serving import CFServer, LadderConfig
    from repro.training.elastic import StragglerMonitor
    cfg = cell.config
    R = datagen.synth_ratings(seed, cfg["n_users"], cfg["n_items"],
                              cfg["n_ratings"], cfg["min_per_user"],
                              cfg["popularity_alpha"])
    window = traffic.schedule(cell.mix, cfg, R, seed, seconds)
    warm = traffic.warmup(cell.mix, cfg, R, seed, window)
    with tempfile.TemporaryDirectory(prefix="bench_fill_") as tmp:
        quiet = LadderConfig(monitor=StragglerMonitor(
            window=64, straggler_ratio=math.inf, hang_timeout_s=math.inf))
        srv = CFServer(R, replace(server_config(cfg, Path(tmp)), ladder=quiet))
        for req in warm + window:
            if req.op == ONBOARD:
                call(srv, req)
        jax.block_until_ready(srv.state)
    done = fill_mark(cache_dir, cell, seconds)
    done.parent.mkdir(parents=True, exist_ok=True)
    done.touch()


def _fill_in_child(root: Path, workload: str, seed: int, seconds: float,
                   cache_dir: Path, require_chip: bool,
                   rate: float | None) -> None:
    """``fill_cache`` in a child process, started before this process
    touches JAX (a process that holds the chip would keep it from the
    child); waits for it to end."""
    import subprocess
    cmd = [sys.executable, "-m", "bench.run", "--fill-cache",
           "--root", str(root), "--cache-dir", str(cache_dir),
           "--workload", workload, "--seed", str(seed),
           "--seconds", repr(seconds)]
    if not require_chip:
        cmd.append("--off-chip")
    if rate is not None:
        cmd += ["--rate", repr(rate)]
    p = subprocess.run(cmd, cwd=ROOT)
    if p.returncode == NO_CHIP_EXIT:
        raise NoChip("the cache-filling process found no TPU")
    if p.returncode != 0:
        raise RuntimeError(f"filling the compile cache failed "
                           f"(exit {p.returncode})")


def _digest(cell) -> str:
    import hashlib
    text = json.dumps([cell.config, cell.mix], sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# The open loop
# ---------------------------------------------------------------------------

@dataclass
class Outcome:
    req: traffic.Request
    start: float = 0.0          # seconds after the window opened
    end: float = 0.0
    ok: bool = False
    info: dict = field(default_factory=dict)

    @property
    def latency(self) -> float:
        return self.end - self.req.due if self.ok else math.inf


def call(srv, req: traffic.Request) -> tuple[bool, dict]:
    a = req.args
    if req.op == ONBOARD:
        s0 = srv.stats.snapshots
        res = srv.onboard_user(a["row"])
        return res.ok, {"user_id": res.user_id, "twin_found": res.twin_found,
                        "rotated": res.rotated, "seq": res.seq,
                        "status": res.status, "rung": res.rung,
                        "n_base": srv.n_base,
                        "snapshots": srv.stats.snapshots - s0}
    if req.op == ADD_RATING:
        return srv.add_rating(a["user"], a["item"], a["stars"]), {}
    q0, u0 = srv.stats.queries, srv.stats.query_unique
    if req.op == RECOMMEND:
        ans = srv.recommend_batch(a["users"], n=a["n"], k_neighbors=a["k"])
        ok = all(len(r) == a["n"] for r in ans)
    else:
        ans = srv.predict_batch(a["users"], a["items"], k=a["k"])
        ok = len(ans) == len(a["users"])
    return ok, {"answer": ans, "rows": srv.stats.queries - q0,
                "unique": srv.stats.query_unique - u0}


class GcMeter:
    """Seconds the cyclic garbage collector pauses the process."""

    def __init__(self):
        self.seconds, self._t = 0.0, 0.0

    def __call__(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def _usage(gc_meter: GcMeter) -> np.ndarray:
    import resource
    u = resource.getrusage(resource.RUSAGE_SELF)
    return np.asarray([u.ru_utime, u.ru_stime, u.ru_minflt, u.ru_majflt,
                       u.ru_nvcsw, u.ru_nivcsw, gc_meter.seconds])


USAGE = ("user_s", "sys_s", "minor_faults", "major_faults",
         "voluntary_switches", "involuntary_switches", "gc_s")


def drive(srv, window: list[traffic.Request], seconds: float
          ) -> tuple[list[Outcome], list[float], float]:
    """Issue the requests in due order, each when due or, when the server
    is still busy, as soon as it is free.  Returns the outcomes, how late
    the generator woke for each request it slept for, and the window's
    length.  Each outcome keeps what its call cost the process (CPU
    seconds, page faults, context switches, garbage-collector pauses) and,
    after a call that rotated, the device's memory counters, so that a
    call far slower than its kind can be told apart afterwards."""
    import jax
    out, late = [], []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"), GcMeter() as gcm:
        for req in window:
            now = time.perf_counter() - t0
            if now > seconds + GRACE_S:
                out.append(Outcome(req, now, now, False, {"status": "late"}))
                continue
            if now < req.due:
                with jax.profiler.TraceAnnotation("wait"):
                    time.sleep(req.due - now)
                late.append(time.perf_counter() - t0 - req.due)
            u0 = _usage(gcm)
            o = Outcome(req, time.perf_counter() - t0)
            with jax.profiler.TraceAnnotation(SPANS[req.op]):
                o.ok, o.info = call(srv, req)
            o.end = time.perf_counter() - t0
            o.info["usage"] = _usage(gcm) - u0
            if o.info.get("rotated"):
                o.info["memory"] = jax.devices()[0].memory_stats()
            out.append(o)
    return out, late, time.perf_counter() - t0


def slowest_calls(outcomes: list[Outcome], n: int = 3) -> list[dict]:
    """The ``n`` slowest calls of the window, each with its place, what
    it did and what it cost the process; beside them the median of the
    calls of the same kind (rotated, or wrote a checkpoint, or neither)."""
    def kind(o):
        return (bool(o.info.get("rotated")), bool(o.info.get("snapshots")))

    def row(o):
        return {"index": outcomes.index(o), "start_s": o.start,
                "seconds": o.end - o.start, "op": o.req.op,
                "rotated": kind(o)[0], "checkpointed": kind(o)[1],
                **dict(zip(USAGE, o.info["usage"].tolist())),
                "memory": o.info.get("memory")}

    timed = [o for o in outcomes if "usage" in o.info]
    slow = sorted(timed, key=lambda o: o.start - o.end)[:n]
    out = []
    for o in slow:
        peers = [p for p in timed if kind(p) == kind(o) and p is not o]
        r = row(o)
        if peers:
            r["peers_median"] = {
                "n": len(peers),
                "seconds": float(np.median([p.end - p.start for p in peers])),
                **dict(zip(USAGE, np.median(
                    [p.info["usage"] for p in peers], axis=0).tolist()))}
        out.append(r)
    return out


# ---------------------------------------------------------------------------
# What the run produced, and the comparison
# ---------------------------------------------------------------------------

def _fetch_rows(srv, users: np.ndarray):
    import jax
    import jax.numpy as jnp
    u = jnp.asarray(users, jnp.int32)
    st = srv.state
    return jax.device_get((st.sim_vals[u], st.sim_idx[u], st.ratings[u]))


def collect_onboard(srv, log: list[Outcome], R: np.ndarray, rng) -> dict:
    """The final arena's geometry, a seeded sample of its rows, and the
    write-ahead log's records."""
    done = [o for o in log if o.req.op == ONBOARD and o.ok]
    n0 = R.shape[0]
    n_active = n0 + len(done)
    users = np.concatenate([
        rng.choice(n0, min(SAMPLE["base_rows"], n0), replace=False),
        rng.choice(np.arange(n0, n_active),
                   min(SAMPLE["new_rows"], len(done)), replace=False),
        np.arange(srv.n_base, n_active)])
    users = np.unique(users)
    vals, idx, ratings = _fetch_rows(srv, users)
    records = {r.seq: r for r in srv.wal.records()} if srv.wal else {}
    return {"n_base": srv.n_base, "n_active": int(srv.state.n_active),
            "capacity": srv.state.capacity, "users": users, "vals": vals,
            "idx": idx, "ratings": ratings, "records": records}


def collect_recovered(cfg: dict, R: np.ndarray, workdir: Path,
                      log: list[Outcome], got: dict) -> dict:
    """What a restarted process loads: ``CFServer.recover`` over the run's
    checkpoint and log directories, once the live server is dropped, read
    at the sampled users and at the rating row of every acknowledged
    onboard whose record the log no longer holds.  A recovery that raises
    is kept as its error."""
    from repro.serving import CFServer
    gone = np.asarray(sorted(o.info["user_id"] for o in log
                             if o.req.op == ONBOARD and o.ok
                             and o.info["seq"] not in got["records"]),
                      np.int64)
    t = time.perf_counter()
    try:
        srv = CFServer.recover(R, server_config(cfg, workdir))
    except Exception as e:          # noqa: BLE001 — nothing was recovered
        return {"error": repr(e), "gone": gone}
    vals, idx, ratings = _fetch_rows(srv, got["users"])
    out = {"n_base": srv.n_base, "n_active": int(srv.state.n_active),
           "vals": vals, "idx": idx, "ratings": ratings, "gone": gone,
           "gone_rows": _fetch_rows(srv, gone)[2],
           "seconds": time.perf_counter() - t}
    del srv
    gc.collect()
    return out


def collect_read(srv, log: list[Outcome], R: np.ndarray, rng) -> dict:
    refreshed = np.unique([o.req.args["user"] for o in log
                           if o.req.op == ADD_RATING and o.ok])
    users = np.unique(np.concatenate([
        rng.choice(R.shape[0], min(SAMPLE["base_rows"], R.shape[0]),
                   replace=False),
        rng.choice(refreshed, min(SAMPLE["refreshed_rows"], len(refreshed)),
                   replace=False)]).astype(np.int64))
    vals, idx, ratings = _fetch_rows(srv, users)
    records = {r.seq: r for r in srv.wal.records()} if srv.wal else {}
    return {"users": users, "vals": vals, "idx": idx, "ratings": ratings,
            "records": records, "n_active": int(srv.state.n_active),
            "capacity": srv.state.capacity}


def check_onboard(cfg: dict, R: np.ndarray, log: list[Outcome], got: dict,
                  rng, control=None) -> tuple[dict, list]:
    """Numbers for an onboarding cell: the sampled rows against exact
    cosines, the sampled twin decisions given the probes the log recorded,
    and each acknowledged onboard read back, from the log or, where a
    checkpoint truncated its record, from the recovered server
    (``got["recovered"]``, which is also held bit for bit to the live
    server).  ``control`` (a lower-precision cosine) writes the rows in
    the server's place."""
    cos = reference.Exact()
    done = sorted((o for o in log if o.req.op == ONBOARD and o.ok),
                  key=lambda o: o.info["user_id"])
    R_all = np.concatenate([R] + [o.req.args["row"][None] for o in done])
    users, n_base = got["users"], got["n_base"]
    live = np.where(users < n_base, n_base, users)
    want = cos.rows(R_all[users], R_all)
    if control is None:
        vals, idx, rat = got["vals"], got["idx"], got["ratings"]
        rows = reference.check_rows(users, live, want, vals, idx, rat,
                                    R_all[users].astype(rat.dtype))
    else:
        vals, idx = _lists_from(control.rows(R_all[users], R_all), live,
                                got["capacity"])
        rows = reference.check_rows(users, live, want, vals, idx)

    notes, twin_wrong = list(rows.notes), 0
    if rows.worst:
        by_uid = {o.info["user_id"]: o.info for o in done}
        for w in rows.worst.values():
            w["onboard"] = ({k: by_uid[w["row"]].get(k)
                             for k in ("rung", "twin_found")}
                            if w["row"] in by_uid else "base")
    row_hash = np.asarray([hash(row.tobytes()) for row in R_all])
    # The probes are in the log's records: the twin decisions are drawn
    # from the onboards whose record a checkpoint has not truncated.
    records = got["records"]
    logged = [o for o in done if o.info["seq"] in records]
    picked = [logged[j] for j in sorted(rng.choice(
        len(logged), min(SAMPLE["twin_checks"], len(logged)),
        replace=False))]
    picked = [(o, records[o.info["seq"]]) for o in picked]
    picked = [(o, r) for o, r in picked
              if r.fields.get("use_twin") and control is None]
    if picked:
        probes = np.unique(np.concatenate(
            [r.arrays["probes"] for _, r in picked]).astype(np.int64))
        psims = dict(zip(probes.tolist(),
                         cos.rows(R_all[probes], R_all)))
        tol = cfg["server"]["sim_tol"]
        for o, r in picked:
            uid, nb = o.info["user_id"], o.info["n_base"]
            p = r.arrays["probes"].astype(np.int64)
            twins = np.asarray([t for t in np.flatnonzero(
                row_hash[:uid] == row_hash[uid])
                if np.array_equal(R_all[t], R_all[uid])], np.int64)
            want_found = reference.twin_decision(
                twins, R_all[uid], p, R_all[p], nb,
                reference.s_max_for(nb), tol,
                np.stack([psims[q] for q in p.tolist()]))
            if o.info["twin_found"] != want_found:
                twin_wrong += 1
                notes.append(f"user {uid}: twin_found="
                             f"{o.info['twin_found']}, reference "
                             f"{want_found}")
    rec = got.get("recovered")
    recovered_rows = {}
    if rec is not None and "error" not in rec:
        recovered_rows = {u: row for u, row in zip(rec["gone"].tolist(),
                                                   rec["gone_rows"])
                          if u < rec["n_active"]}
    # The newest checkpoint the live server took, by its own counter: a
    # truncated record has to lie at or below it.
    checkpoint_seq = max((o.info["seq"] for o in done
                          if o.info["snapshots"]), default=0)

    def read_back(o) -> bool:
        row = o.req.args["row"].astype(np.float32)
        r = records.get(o.info["seq"])
        if r is not None:
            return np.array_equal(r.arrays["ratings"], row)
        uid = o.info["user_id"]
        return (o.info["seq"] <= checkpoint_seq and uid in recovered_rows
                and np.array_equal(recovered_rows[uid], row))

    numbers = {"sim_err": rows.sim_err, "unit_err": rows.unit_err,
               "rows_wrong": rows.rows_wrong, "twin_wrong": twin_wrong,
               "wal_missing": sum(not read_back(o) for o in done)}
    if rec is not None:
        numbers["recovered_wrong"] = _recovered_wrong(got, rec)
        numbers["wal_truncated"] = int(rec["gone"].size)
        notes.append(f"recovery: {rec['error']}" if "error" in rec else
                     f"recovery: {rec['gone'].size} acknowledged onboards "
                     f"read back from a checkpoint, {len(logged)} from the "
                     f"log, {len(picked)} twin decisions checked, "
                     f"{rec['seconds']!r} s")
    notes.append("lists " + json.dumps(rows.summary()))
    return numbers, notes


def _recovered_wrong(got: dict, rec: dict) -> int:
    """Mismatches of the recovered server against the live one at the
    window's end: its ``n_active``, its ``n_base``, and each sampled
    user whose list values, ids or ratings differ in any bit.  A recovery
    that raised misses all of them."""
    n = len(got["users"])
    if "error" in rec:
        return n + 2
    wrong = np.zeros(n, bool)
    for key in ("vals", "idx", "ratings"):
        a, b = np.asarray(got[key]), np.asarray(rec[key])
        if a.shape != b.shape or a.dtype != b.dtype:
            return n + 2
        wrong |= (np.ascontiguousarray(a).view(np.uint8).reshape(n, -1)
                  != np.ascontiguousarray(b).view(np.uint8).reshape(n, -1)
                  ).any(axis=1)
    return (int(rec["n_active"] != got["n_active"])
            + int(rec["n_base"] != got["n_base"]) + int(wrong.sum()))


class _Replay:
    """One cosine backend following the writes in issue order: each
    user's list as it was last written (built at start, or refreshed by
    that user's own write)."""

    def __init__(self, cos, R0: np.ndarray, users):
        self.cos = cos
        built = type(cos)()              # the build saw the base ratings
        built.start(R0)
        users = np.unique(np.asarray(users, np.int64))
        self.base = dict(zip(users.tolist(), built.rows_of(users)))
        cos.start(R0)
        self.asof: dict[int, np.ndarray] = {}

    def write(self, u: int, i: int, v: float) -> None:
        self.cos.set(u, i, v)
        self.asof[u] = self.cos.rows_of([u])[0]

    def sims(self, u: int) -> np.ndarray:
        return self.asof[u] if u in self.asof else self.base[u]


def check_read(cfg: dict, R0: np.ndarray, log: list[Outcome], got: dict,
               rng, control=None) -> tuple[dict, list]:
    """Numbers for a read cell: a seeded sample of the window's read
    requests and of the lists (base rows as built, rows the writes
    refreshed), replayed over the log in issue order.  ``control`` (a
    lower-precision cosine) answers in the server's place."""
    reads = [i for i, o in enumerate(log) if o.req.op in READS and o.ok
             and o.info.get("phase") == "window"]
    sample = set(rng.choice(reads, min(SAMPLE["read_requests"], len(reads)),
                            replace=False).tolist())
    R = R0.astype(np.int8).copy()
    needed = [np.asarray(got["users"])] + [
        np.asarray(log[i].req.args["users"]) for i in sample]
    needed = np.concatenate(needed)
    exact = _Replay(reference.Exact(), R0, needed)
    lower = _Replay(control, R0, needed) if control is not None else None
    out = reference.ReadCheck()

    for i, o in enumerate(log):
        a = o.req.args
        if o.req.op == ADD_RATING:
            if o.ok:
                R[a["user"], a["item"]] = int(a["stars"])
                exact.write(a["user"], a["item"], a["stars"])
                if lower is not None:
                    lower.write(a["user"], a["item"], a["stars"])
            continue
        if i not in sample:
            continue
        seen = set()
        for b, u in enumerate(np.asarray(a["users"]).tolist()):
            key = (u, int(a["items"][b])) if "items" in a else u
            if key in seen:
                continue
            seen.add(key)
            nbrs, s, amb = reference.neighbours(exact.sims(u), u, a["k"])
            if amb:
                out.ambiguous += 1
                continue
            out.compared += 1
            got_ans = o.info["answer"][b] if lower is None else \
                _answer(control, lower.sims(u), R, u, a, b)
            if o.req.op == RECOMMEND:
                reference.compare_recommend(
                    reference.scores(R, u, nbrs, s), got_ans, a["n"], out,
                    f"recommend({u})")
            else:
                want = reference.prediction(R, nbrs, s, int(a["items"][b]))
                out.pred_err = max(out.pred_err, abs(float(got_ans) - want))

    users = got["users"]
    want = np.stack([exact.sims(int(u)) for u in users])
    live = np.full(len(users), R0.shape[0])
    if lower is None:
        rows = reference.check_rows(users, live, want, got["vals"],
                                    got["idx"], got["ratings"],
                                    R[users].astype(np.float32))
    else:
        vals, idx = _lists_from(np.stack([lower.sims(int(u)) for u in users]),
                                live, got["capacity"])
        rows = reference.check_rows(users, live, want, vals, idx)
    logged = {(int(r.fields["user"]), int(r.fields["item"]),
               float(r.fields["rating"]))
              for r in got["records"].values() if r.op == "add_rating"}
    wal_missing = sum((o.req.args["user"], o.req.args["item"],
                       o.req.args["stars"]) not in logged
                      for o in log if o.req.op == ADD_RATING and o.ok)
    # Recommendations and predictions are held to one widest gap.
    return ({"read_err": max(out.rec_err, out.pred_err),
             "rec_wrong": out.rec_wrong, "sim_err": rows.sim_err,
             "unit_err": rows.unit_err, "rows_wrong": rows.rows_wrong,
             "wal_missing": wal_missing},
            out.notes + rows.notes + [
                f"{out.compared} read rows compared, {out.ambiguous} left "
                f"out as ambiguous; widest gap of a recommended score "
                f"{out.rec_err!r}, of a prediction {out.pred_err!r}",
                "lists " + json.dumps(rows.summary())])


def _answer(control, sims: np.ndarray, R: np.ndarray, u: int, a: dict,
            b: int):
    """A read answered from ``sims`` with the control's arithmetic."""
    nbrs, s, _ = reference.neighbours(sims, u, a["k"])
    if "items" in a:
        return control.prediction(R, nbrs, s, int(a["items"][b]))
    sc = control.scores(R, u, nbrs, s)
    top = np.argsort(-sc, kind="stable")[:a["n"]]
    return [(int(t), float(sc[t])) for t in top]


def _lists_from(sims: np.ndarray, live: np.ndarray, width: int):
    rows = [reference.expected_row(s, n, width) for s, n in zip(sims, live)]
    return (np.stack([v for v, _ in rows]).astype(np.float32),
            np.stack([i for _, i in rows]))


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    """What a metric's reader reads: the window's outcomes, the server's
    counters before and after it, compile activity inside it, set-up time
    and, in a traced run, the reduced trace and the device's peaks."""
    outcomes: list
    stats0: dict
    stats1: dict
    rotation_ms: list
    compile: dict
    window_s: float
    setup_s: float
    config: dict
    trace: object = None
    peaks: dict | None = None

    def latencies_ms(self, ops) -> list[float]:
        """Due time to acknowledgement of each request of ``ops``; a
        failed request is infinite."""
        return [o.latency * 1e3 for o in self.outcomes if o.req.op in ops]

    def delta(self, counter: str) -> int:
        return self.stats1[counter] - self.stats0[counter]


def percentile(values: list[float], q: float) -> float | None:
    """The q-th percentile by nearest rank (Python's sorted order, so an
    infinite latency counts as the slowest)."""
    if not values:
        return None
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100 * len(xs)) - 1))]


def _stats(srv) -> dict:
    return {k: v for k, v in vars(srv.stats).items()
            if isinstance(v, (int, float))}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             control: bool = False, keep_trace: Path | None = None,
             cache_dir: Path = ROOT / ".jax_cache",
             rate: float | None = None) -> dict:
    """One run; returns the result line.  ``control`` swaps the server's
    answers for the lower-precision reference's (``bench/control.py``)
    before the comparison; ``rate`` replaces the mix's arrival rate (for
    a sweep to the knee)."""
    cell = spec.load(root, workload, rate)
    onboarding = any(op["op"] == ONBOARD for op in cell.mix["ops"])
    phases = {"imports": time.perf_counter() - T_START}
    if onboarding and not fill_mark(cache_dir, cell, seconds).exists():
        t = time.perf_counter()
        _fill_in_child(root, workload, seed, seconds, cache_dir,
                       require_chip, rate)
        phases["fill_cache"] = time.perf_counter() - t
    t = time.perf_counter()
    device = device_info(cell.workload["chips"], require_chip)
    phases["jax_start"] = time.perf_counter() - t
    configure_jax(cache_dir)
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    t = time.perf_counter()
    from repro.serving import CFServer
    phases["program_import"] = time.perf_counter() - t

    t = time.perf_counter()
    cfg = cell.config
    R = datagen.synth_ratings(seed, cfg["n_users"], cfg["n_items"],
                              cfg["n_ratings"], cfg["min_per_user"],
                              cfg["popularity_alpha"])
    window = traffic.schedule(cell.mix, cfg, R, seed, seconds)
    warm = traffic.warmup(cell.mix, cfg, R, seed, window)
    log: list[Outcome] = []
    phases["data"] = time.perf_counter() - t

    with tempfile.TemporaryDirectory(prefix="bench_") as tmp, \
            CompileMeter() as meter:
        t = time.perf_counter()
        srv = CFServer(R, server_config(cfg, Path(tmp)))
        jax.block_until_ready(srv.state)
        phases["build"] = time.perf_counter() - t
        t = time.perf_counter()
        for req in warm:
            ok, info = call(srv, req)
            if not ok:
                raise RuntimeError(f"warm-up {req.op} failed: {info}")
            log.append(Outcome(req, 0.0, 0.0, ok, {**info, "phase": "warmup"}))
        jax.block_until_ready(srv.state)
        phases["warmup"] = time.perf_counter() - t
        # Write back what set-up and earlier processes left dirty, so
        # that the window's log syncs wait for their own records only.
        t = time.perf_counter()
        os.sync()
        phases["sync"] = time.perf_counter() - t
        setup_s = time.perf_counter() - T_START
        stats0, rot0 = _stats(srv), len(srv.stats.rotation_ms)
        compile0 = meter.snapshot()
        trace_dir = Path(tmp) / "trace"
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
        outcomes, late, window_s = drive(srv, window, seconds)
        jax.block_until_ready(srv.state)
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            from bench import trace as trace_lib
            reduced = trace_lib.reduce_dir(trace_dir)
            if keep_trace is not None:
                import shutil
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        compile1 = meter.snapshot()
        stats1 = _stats(srv)
        rotation_ms = list(srv.stats.rotation_ms)[rot0:]
        mem = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
        for o in outcomes:
            o.info["phase"] = "window"
        log += outcomes

        rng = np.random.default_rng([int(seed) % (1 << 63), 7])
        got = (collect_onboard if onboarding else collect_read)(
            srv, log, R, rng)
        del srv
        gc.collect()
        # Where checkpoints truncate the log, a restart has to recover
        # the acknowledged onboards the log no longer holds.
        if onboarding and cfg["server"]["snapshot"]["disk"]:
            got["recovered"] = collect_recovered(cfg, R, Path(tmp), log, got)

    # The comparison, once the server is gone.
    lower = None
    if control:
        from bench.control import Lower
        lower = Lower()
    numbers, notes = (check_onboard if onboarding else check_read)(
        cfg, R, log, got, rng, lower)
    correct, checks = reference.verdict(numbers, cfg["limits"])

    rec = RunRecord(outcomes=outcomes, stats0=stats0, stats1=stats1,
                    rotation_ms=rotation_ms,
                    compile=_delta(compile0, compile1), window_s=window_s,
                    setup_s=setup_s, config=cfg, trace=reduced)
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        if require_chip:
            rec.peaks = spec.device_peaks(cell.peaks, device["kind"])
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(root, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    failed = sum(not o.ok for o in outcomes)
    info = {"workload": workload, "seed": seed, "window_s": window_s,
            "requests": len(outcomes), "failed": failed,
            "served_s": sum(o.end - o.start for o in outcomes),
            "longest_call_s": max((o.end - o.start for o in outcomes),
                                  default=0.0),
            "ops": {op: sum(o.req.op == op for o in outcomes)
                    for op in SPANS},
            "service_ms": {op: float(np.mean([(o.end - o.start) * 1e3
                                              for o in outcomes
                                              if o.req.op == op]))
                           for op in SPANS
                           if any(o.req.op == op for o in outcomes)},
            "drain_s": window_s - max((o.req.due for o in outcomes),
                                      default=0.0),
            "recommend_unique": [o.info["unique"] for o in outcomes
                                 if o.req.op == RECOMMEND and o.ok],
            "generator_late_ms": _late_summary(late),
            "slowest_calls": slowest_calls(outcomes),
            "setup_phases_s": phases,
            "stats_delta": {k: stats1[k] - stats0[k] for k in stats0
                            if stats1[k] != stats0[k]},
            "compile_in_window": rec.compile,
            "readings": numbers,
            "notes": notes[:20]}
    result = {"correct": bool(correct), "attempted": len(outcomes),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return {"info": info, "result": result}


def _delta(a: dict, b: dict) -> dict:
    return {"seconds": {k: b["seconds"][k] - a["seconds"][k]
                        for k in a["seconds"]},
            "programs": b["programs"] - a["programs"],
            "cache_hits": b["cache_hits"] - a["cache_hits"],
            "compiled": b["compiled"][len(a["compiled"]):]}


def _late_summary(late: list[float]) -> dict:
    if not late:
        return {"n": 0}
    ms = np.asarray(late) * 1e3
    return {"n": int(ms.size), "p50": float(np.median(ms)),
            "p99": float(np.percentile(ms, 99)), "max": float(ms.max())}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", type=Path, default=None,
                   help="copy the profiler's trace directory here")
    p.add_argument("--rate", type=float, default=None,
                   help="arrivals per second in place of the mix's (a sweep "
                        "to the knee)")
    # The cache-filling child process (``_fill_in_child``).
    p.add_argument("--fill-cache", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--root", type=Path, default=ROOT, help=argparse.SUPPRESS)
    p.add_argument("--cache-dir", type=Path, default=ROOT / ".jax_cache",
                   help=argparse.SUPPRESS)
    p.add_argument("--off-chip", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    try:
        if args.fill_cache:
            fill_cache(args.root, args.workload, args.seed, args.seconds,
                       args.cache_dir, not args.off_chip, args.rate)
            return 0
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace,
                       rate=args.rate)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP_EXIT
    print(json.dumps(out["info"]), flush=True)
    for name, c in out["result"]["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

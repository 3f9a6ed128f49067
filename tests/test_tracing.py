"""The server's spans (``serving/tracing.py``) in a profiler trace.

A tiny ``CFServer`` with a WAL, a health sweep after every onboard and
room for two new users is driven under ``jax.profiler`` through onboards
(the third rotates the arena), a recommend batch, a predict batch and a
rating update, each inside a request span as the benchmark opens them;
then a second one that rotates incrementally, through onboards whose
slices and swap run inside them.  The trace, reduced by
``bench/spans.py``, holds every name of ``SPANS`` inside the request it
belongs to, each stage once per call (a rotation's slice and swap once
each per slice and swap); and the traced servers answer bit for bit as
untraced ones do.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.serving import (CFServer, RotationConfig, ServerConfig,
                           SnapshotConfig, WalConfig)
from repro.serving.tracing import SPANS

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

READS = {"recommend_batch", "predict_batch"}
BELONGS = {"cf.onboard.rotate": {"onboard_user"},
           "cf.onboard.run": {"onboard_user"},
           "cf.health_check": {"onboard_user"},
           "cf.wal.append": {"onboard_user", "add_rating"},
           "cf.add_rating.apply": {"add_rating"},
           "cf.rotation.step": {"onboard_user"},
           "cf.rotation.swap": {"onboard_user"}}
ONCE_PER_CALL = [("cf.onboard.run", {"onboard_user"}),
                 ("cf.add_rating.apply", {"add_rating"})] + [
    (s, READS) for s in SPANS if s.startswith("cf.read.")]


def _ratings(n=12, m=10):
    rng = np.random.default_rng(13)
    R = (rng.integers(1, 6, (n, m)) * (rng.random((n, m)) < 0.5)
         ).astype(np.float32)
    R[R.sum(axis=1) == 0, 0] = 3.0
    return R


def _drive(srv, R):
    """(request span, answer) of each call, the answers as plain values."""
    fresh = np.zeros(R.shape[1], np.float32)
    fresh[[1, 4]] = [5.0, 2.0]
    calls = [
        ("onboard_user", lambda: srv.onboard_user(R[3].copy())),
        ("onboard_user", lambda: srv.onboard_user(fresh)),
        ("onboard_user", lambda: srv.onboard_user(R[5].copy())),  # rotates
        ("recommend_batch", lambda: srv.recommend_batch(
            [0, 3, 12, 5, 3], n=3, k_neighbors=4)),
        ("predict_batch", lambda: srv.predict_batch(
            [1, 12, 13], [2, 4, 7], k=4)),
        ("add_rating", lambda: srv.add_rating(2, 6, 4.0)),
        ("recommend_batch", lambda: srv.recommend_batch(
            [2, 7], n=3, k_neighbors=4)),
    ]
    out = []
    for name, call in calls:
        with jax.profiler.TraceAnnotation(name):
            ans = call()
        if name == "onboard_user":
            ans = (ans.status, ans.user_id, ans.twin_found, ans.rotated)
        out.append((name, ans))
    return out


def _drive_incremental(srv, R):
    """Four onboards into a server rotating in slices: the second starts
    the plan, the second to fourth run its three slices, the fourth
    swaps."""
    fresh = np.zeros(R.shape[1], np.float32)
    fresh[[2, 7]] = [4.0, 1.0]
    out = []
    for row in (R[3].copy(), fresh, R[5].copy(), R[7].copy()):
        with jax.profiler.TraceAnnotation("onboard_user"):
            res = srv.onboard_user(row)
        out.append((res.status, res.user_id, res.twin_found, res.rotated))
    return out


def _server(tmp: Path, R):
    return CFServer(R, ServerConfig(
        capacity_extra=2, snapshot=SnapshotConfig(every=2, check_every=1),
        wal=WalConfig(dir=str(tmp))))


def _incremental_server(tmp: Path, R):
    return CFServer(R, ServerConfig(
        capacity_extra=4, wal=WalConfig(dir=str(tmp)),
        rotation=RotationConfig(budget_rows=4, reserve_slots=3)))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from bench import spans
    R = _ratings()
    tmp = tmp_path_factory.mktemp("tracing")
    plain = _server(tmp / "wal_plain", R)
    want = _drive(plain, R)
    plain_inc = _incremental_server(tmp / "wal_plain_inc", R)
    want_inc = _drive_incremental(plain_inc, R)
    srv = _server(tmp / "wal_traced", R)
    inc = _incremental_server(tmp / "wal_traced_inc", R)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            got = _drive(srv, R)
            got_inc = _drive_incremental(inc, R)
        jax.block_until_ready((srv.state, inc.state))
    finally:
        jax.profiler.stop_trace()
    return {"want": want, "got": got, "plain": plain, "srv": srv,
            "want_inc": want_inc, "got_inc": got_inc,
            "plain_inc": plain_inc, "inc": inc,
            "trace": spans.reduce_dir(tmp / "trace")}


def test_traced_answers_are_bit_identical(traced):
    assert traced["got"] == traced["want"]
    assert [a for n, a in traced["got"] if n == "onboard_user"][2][3], \
        "the third onboard should rotate the arena"
    assert traced["got_inc"] == traced["want_inc"]
    assert [a[3] for a in traced["got_inc"]] == [False] * 3 + [True], \
        "the fourth incremental onboard should swap"
    for plain, srv in (("plain", "srv"), ("plain_inc", "inc")):
        a = jax.tree_util.tree_leaves(traced[plain].state)
        b = jax.tree_util.tree_leaves(traced[srv].state)
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))


def test_every_span_inside_its_request(traced):
    t = traced["trace"]
    assert {n for n, _, _ in t.program_spans} == set(SPANS)
    requests = [s for s in t.spans if s[0] not in ("window", "wait")]
    for name, s, e in t.program_spans:
        around = [r for r, rs, re in requests if rs <= s and e <= re]
        assert len(around) == 1, (name, around)
        assert around[0] in BELONGS.get(name, READS), (name, around)


def test_each_stage_once_per_call(traced):
    t = traced["trace"]
    for stage, requests in ONCE_PER_CALL:
        for request, rs, re in t.requests(requests):
            n = sum(1 for name, s, e in t.program_spans
                    if name == stage and rs <= s and e <= re)
            assert n == 1, (stage, request, n)


def test_rotation_spans_once_per_slice_and_swap(traced):
    """``cf.rotation.step`` opens once per slice the server counts and
    ``cf.rotation.swap`` once per swap, each at most once inside a call,
    the swap after that call's slice."""
    t, inc = traced["trace"], traced["inc"]
    steps = [s for s in t.program_spans if s[0] == "cf.rotation.step"]
    swaps = [s for s in t.program_spans if s[0] == "cf.rotation.swap"]
    assert len(steps) == inc.stats.plan_steps == 3
    assert len(swaps) == inc.stats.rotations == 1
    assert inc.stats.forced_drains == 0
    for _, rs, re in t.requests({"onboard_user"}):
        inside = [(n, s) for n, s, e in steps + swaps if rs <= s and e <= re]
        assert len(inside) == len({n for n, _ in inside}), inside
    assert steps[-1][2] <= swaps[0][1]
    assert 0 < inc.stats.plan_step_s and 0 < inc.stats.rotation_pause_s


def test_rotation_pause_counts_synchronous_rotation(traced):
    """With ``budget_rows`` 0 the one rotation is the stall of the onboard
    that found the arena full: ``rotation_pause_s`` counts it, no slice
    ran, and the ring holds the same milliseconds."""
    stats = traced["srv"].stats
    assert stats.rotations == 1 and stats.plan_steps == 0
    assert stats.rotation_pause_s > 0 and stats.plan_step_s == 0
    assert list(stats.rotation_pause_ms) == [stats.rotation_pause_s * 1e3]

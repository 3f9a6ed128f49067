"""Batched query-path throughput at MovieLens scale.

Four rungs of the same read, B users per request batch:

  * ``scalar_loop``   — the pre-PR-10 serving path: one jitted
                        ``knn.recommend`` dispatch per user plus the
                        per-element ``float()``/``int()`` host syncs.
  * ``batched``       — ``knn.recommend_batch`` (vmapped scalar path,
                        row-wise bit-identical), one dispatch + one
                        ``jax.device_get`` for the whole batch.
  * ``batched_kernel``— probe (``top_k_neighbors_batch``) + the fused
                        ``knn_score`` scoring path + on-device top-n.
                        Backend auto-selects: the Pallas kernel on TPU,
                        the einsum on CPU (interpret-mode Pallas would
                        only benchmark the emulator).
  * ``dedup``         — the full ``CFServer.recommend_batch`` endpoint
                        (guards + twin dedup + fan-out) under a
                        twin-fraction sweep: ``twin{f}`` means fraction f
                        of the batch's rows duplicate a small hot set —
                        the query-side analogue of the paper's identical
                        new users.

CSV rows are ``query_{rung}_B{B}[...]`` with median wall microseconds
per *batch*; ``derived`` carries rows/s and the speedup over the scalar
loop at the same B.  Bit-exactness of batched vs scalar is asserted, not
just benchmarked.  ``REPRO_BENCH_FAST=1`` shrinks shapes to a
compile-check (CI smoke) and additionally forces one interpret-mode run
of the Pallas kernel so TPU-targeted code is exercised on every push.
"""
from __future__ import annotations

import os
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import CSV, time_call
from repro.core import build_state, knn
from repro.kernels.knn_score.ops import knn_recommend_topn

FAST = os.environ.get("REPRO_BENCH_FAST", "") not in ("", "0")

N_USERS, N_ITEMS = (100, 64) if FAST else (943, 1682)   # MovieLens-100k
BATCHES = (1, 16) if FAST else (1, 16, 256)
TWIN_FRACTIONS = (0.5,) if FAST else (0.0, 0.5, 0.9)
K_NEIGHBORS, N_REC = 20, 10
HOT_SET = 4                      # distinct users the twin rows draw from


def _ratings(rng, n, m, density=0.06):
    R = (rng.integers(1, 6, (n, m)) * (rng.random((n, m)) < density)
         ).astype(np.float32)
    R[R.sum(axis=1) == 0, 0] = 3.0
    return R


def _scalar_loop(state, users_np, rec_jit):
    """The old serving read path: one dispatch + per-element host sync
    per user."""
    out = []
    for u in users_np:
        scores, items = rec_jit(state, jnp.int32(int(u)))
        out.append([(int(i), float(s)) for s, i in zip(scores, items)])
    return out


def _batched(state, users_dev, batch_jit):
    scores, items = jax.device_get(batch_jit(state, users_dev))
    return [[(int(i), float(s)) for s, i in zip(sr, ir)]
            for sr, ir in zip(scores, items)]


def main(csv: CSV) -> None:
    rng = np.random.default_rng(0)
    R = _ratings(rng, N_USERS, N_ITEMS)
    state = jax.jit(lambda r: build_state(r, capacity_extra=8))(
        jnp.asarray(R))
    state = jax.block_until_ready(state)

    def _probe(st, us):
        sims, nbrs = knn.top_k_neighbors_batch(st, us, K_NEIGHBORS)
        return jnp.maximum(sims, 0.0), nbrs

    rec_jit = jax.jit(lambda st, u: knn.recommend(st, u, K_NEIGHBORS, N_REC))
    batch_jit = jax.jit(lambda st, us: knn.recommend_batch(
        st, us, K_NEIGHBORS, N_REC))
    kernel_jit = jax.jit(lambda st, us: knn_recommend_topn(
        st.ratings, *_probe(st, us), us, N_REC))

    repeats = 1 if FAST else 3
    for B in BATCHES:
        users_np = rng.integers(0, N_USERS, B).astype(np.int32)
        users_dev = jnp.asarray(users_np)

        # bit-exactness gate before any timing
        ref = _scalar_loop(state, users_np, rec_jit)
        got = _batched(state, users_dev, batch_jit)
        if ref != got:
            raise AssertionError(f"batched != scalar at B={B}")

        t_scalar = time_call(lambda s, u=users_np: _scalar_loop(
            s, u, rec_jit), state, warmup=1, repeats=repeats)
        t_batch = time_call(batch_jit, state, users_dev, repeats=repeats)
        t_kernel = time_call(kernel_jit, state, users_dev, repeats=repeats)
        csv.add(f"query_scalar_loop_B{B}", t_scalar,
                f"rows_per_s={B / t_scalar:.0f}")
        csv.add(f"query_batched_B{B}", t_batch,
                f"rows_per_s={B / t_batch:.0f} "
                f"speedup={t_scalar / t_batch:.2f}")
        csv.add(f"query_batched_kernel_B{B}", t_kernel,
                f"rows_per_s={B / t_kernel:.0f} "
                f"speedup={t_scalar / t_kernel:.2f}")

    # full serving endpoint with twin dedup, twin-fraction sweep
    from repro.serving import CFServer, ServerConfig
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        srv = CFServer(R, ServerConfig(capacity_extra=8))
    B = BATCHES[-1]
    hot = rng.integers(0, N_USERS, HOT_SET)
    for f in TWIN_FRACTIONS:
        users = rng.integers(0, N_USERS, B)
        twin_rows = rng.random(B) < f
        users[twin_rows] = hot[rng.integers(0, HOT_SET, int(twin_rows.sum()))]
        q0, u0 = srv.stats.queries, srv.stats.query_unique
        srv.recommend_batch(users, n=N_REC, k_neighbors=K_NEIGHBORS)  # warm
        savings = 1.0 - (srv.stats.query_unique - u0) / (srv.stats.queries
                                                         - q0)
        t = time_call(lambda _s, u=users: srv.recommend_batch(
            u, n=N_REC, k_neighbors=K_NEIGHBORS), state, warmup=1,
            repeats=repeats)
        csv.add(f"query_dedup_B{B}_twin{f}", t,
                f"rows_per_s={B / t:.0f} savings={savings:.2f}")

    if FAST:
        # CI compile-check: force the Pallas kernel once (interpreted off
        # the TPU) so TPU-targeted code paths stay green on every push.
        us = jnp.asarray(rng.integers(0, N_USERS, 4).astype(np.int32))
        w, nbrs = _probe(state, us)
        out = knn_recommend_topn(state.ratings, w, nbrs, us, N_REC,
                                 use_pallas=True)
        jax.block_until_ready(out)
        csv.add("query_kernel_interpret_smoke", 0.0, "compiled=1")


if __name__ == "__main__":
    c = CSV()
    c.header()
    main(c)

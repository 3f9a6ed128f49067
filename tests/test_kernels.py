"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpreted on
the CPU; ``test_tpu_compile.py`` compiles the main-path kernels for a
v5e)."""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels import (cosine_similarity, embedding_bag, knn_scores,
                           knn_recommend_topn, merge_insert, twin_probe,
                           verify_rows)
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.knn_score.ref import knn_scores_ref
from repro.kernels.list_merge.ref import merge_insert_ref
from repro.kernels.similarity.ref import similarity_ref
from repro.kernels.twin_probe.ref import twin_probe_ref
from repro.kernels.verify_rows.ref import verify_rows_ref


@pytest.mark.parametrize("nq,n,m", [(8, 16, 32), (37, 451, 300),
                                    (128, 256, 512), (1, 943, 1682),
                                    (130, 259, 515)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_similarity_sweep(nq, n, m, dtype):
    rng = np.random.default_rng(nq * 1000 + n)
    Q = jnp.asarray(rng.normal(size=(nq, m)).astype(np.float32)).astype(
        dtype)
    R = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32)).astype(
        dtype)
    out = cosine_similarity(Q, R)
    qn = jnp.linalg.norm(Q.astype(jnp.float32), axis=1)
    rn = jnp.linalg.norm(R.astype(jnp.float32), axis=1)
    ref = similarity_ref(Q, R, qn, rn)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)


@pytest.mark.parametrize("c,N", [(2, 64), (8, 700), (16, 2048), (8, 513)])
def test_twin_probe_sweep(c, N):
    rng = np.random.default_rng(c * N)
    rows = jnp.asarray(rng.uniform(0, 1, (c, N)).astype(np.float32))
    s0 = rows[:, N // 3]
    mask, count = twin_probe(rows, s0, tol=1e-6)
    mref, cref = twin_probe_ref(rows, s0, 1e-6)
    assert np.array_equal(np.asarray(mask), np.asarray(mref))
    assert int(count) == int(cref)


@pytest.mark.parametrize("s,m", [(8, 16), (37, 211), (256, 512), (300, 700)])
@pytest.mark.parametrize("dtype", [np.float32, np.int8])
def test_verify_rows_sweep(s, m, dtype):
    rng = np.random.default_rng(s * m)
    C = jnp.asarray(rng.integers(0, 6, (s, m)).astype(dtype))
    r0 = C[s // 2]
    valid = jnp.asarray(rng.random(s) < 0.8)
    out = verify_rows(C, r0, valid)
    ref = verify_rows_ref(C, r0, valid)[:, 0]
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("nb,hot,V,dim", [(4, 2, 50, 8), (16, 8, 1000, 128),
                                          (33, 5, 200, 64)])
def test_embedding_bag_sweep(nb, hot, V, dim):
    rng = np.random.default_rng(nb * hot)
    table = jnp.asarray(rng.normal(size=(V, dim)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, V, (nb, hot)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 1, (nb, hot)).astype(np.float32))
    mask = jnp.asarray(rng.random((nb, hot)) < 0.7)
    out = embedding_bag(table, idx, w, mask)
    ref = embedding_bag_ref(table, idx, w * mask.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _merge_case(rng, R, L, k):
    """Sorted rows with sentinel heads + duplicate-heavy inserts."""
    pool = np.concatenate([[-2.0, -2.0],
                           np.round(rng.uniform(-1, 1, 8), 2)])
    vals = np.sort(rng.choice(pool, size=(R, L)).astype(np.float32), axis=1)
    idx = np.stack([rng.permutation(L).astype(np.int32) for _ in range(R)])
    ins_vals = np.round(rng.uniform(-1.9, 1, (R, k)), 2).astype(np.float32)
    ins_vals[0, 0] = vals[0, L // 2]             # tie vs an existing entry
    if k > 1:
        ins_vals[:, 1] = ins_vals[:, 0]          # tie between inserts
    ins_idx = np.broadcast_to(1000 + np.arange(k, dtype=np.int32), (R, k))
    ins_mask = rng.random((R, k)) < 0.7
    return (jnp.asarray(vals), jnp.asarray(idx), jnp.asarray(ins_vals),
            jnp.asarray(np.ascontiguousarray(ins_idx)),
            jnp.asarray(ins_mask))


@pytest.mark.parametrize("R,L,k", [(5, 12, 3), (9, 33, 7), (16, 64, 1),
                                   (3, 8, 8), (11, 130, 30), (8, 128, 5)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_merge_insert_sweep(R, L, k, use_pallas):
    rng = np.random.default_rng(R * 1000 + L + k)
    vals, idx, iv, ii, mask = _merge_case(rng, R, L, k)
    out_v, out_i = merge_insert(vals, idx, iv, ii, mask,
                                use_pallas=use_pallas)
    ref_v, ref_i = merge_insert_ref(vals, idx, iv, ii, mask)
    assert np.array_equal(np.asarray(out_v), np.asarray(ref_v))
    assert np.array_equal(np.asarray(out_i), np.asarray(ref_i))
    # merged rows stay ascending
    assert bool(jnp.all(out_v[:, 1:] >= out_v[:, :-1]))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_merge_insert_equals_sequential(use_pallas):
    """The batched merge == k sequential drop-min shift-inserts."""
    rng = np.random.default_rng(7)
    vals, idx, iv, ii, mask = _merge_case(rng, 6, 24, 5)
    seq_v, seq_i = np.asarray(vals).copy(), np.asarray(idx).copy()
    for t in range(5):
        for r in range(6):
            if not bool(mask[r, t]):
                continue
            s = float(iv[r, t])
            p = int(np.searchsorted(seq_v[r], s, side="right"))
            if p == 0:
                continue                          # below min: dropped
            seq_v[r] = np.concatenate([seq_v[r, 1:p], [s], seq_v[r, p:]])
            seq_i[r] = np.concatenate([seq_i[r, 1:p], [int(ii[r, t])],
                                       seq_i[r, p:]])
    out_v, out_i = merge_insert(vals, idx, iv, ii, mask,
                                use_pallas=use_pallas)
    assert np.array_equal(np.asarray(out_v), seq_v.astype(np.float32))
    assert np.array_equal(np.asarray(out_i), seq_i)


def _knn_case(rng, B, k, N, m):
    """Sparse ratings + clamped weights with dead (zero-weight) slots."""
    R = (rng.integers(1, 6, (N, m)) * (rng.random((N, m)) < 0.3)
         ).astype(np.float32)
    w = np.maximum(rng.normal(size=(B, k)), 0.0).astype(np.float32)
    nbrs = rng.integers(0, N, (B, k)).astype(np.int32)
    users = rng.integers(0, N, B).astype(np.int32)
    return (jnp.asarray(R), jnp.asarray(w), jnp.asarray(nbrs),
            jnp.asarray(users))


@pytest.mark.parametrize("B,k,N,m", [(4, 5, 30, 17), (16, 10, 120, 50),
                                     (1, 20, 64, 130), (7, 3, 50, 512),
                                     (13, 1, 16, 600)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_knn_scores_sweep(B, k, N, m, use_pallas):
    """Both backends (scan fast path / interpret-mode Pallas) are
    bit-exact against the einsum oracle."""
    rng = np.random.default_rng(B * 1000 + k * 100 + N + m)
    R, w, nbrs, users = _knn_case(rng, B, k, N, m)
    out = knn_scores(R, w, nbrs, users, use_pallas=use_pallas)
    ref = knn_scores_ref(R, w, nbrs, users)
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_knn_scores_zero_weight_slot_is_noop(use_pallas):
    """A weight-0 slot (SENTINEL/padded neighbour after clamping) must
    not perturb scores no matter which row it points at."""
    rng = np.random.default_rng(99)
    R, w, nbrs, users = _knn_case(rng, 6, 4, 40, 33)
    w = w.at[:, 2].set(0.0)
    a = knn_scores(R, w, nbrs, users, use_pallas=use_pallas)
    b = knn_scores(R, w, nbrs.at[:, 2].set(0), users,
                   use_pallas=use_pallas)
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_knn_recommend_topn_masks_seen(use_pallas):
    rng = np.random.default_rng(5)
    R, w, nbrs, users = _knn_case(rng, 5, 6, 30, 24)
    scores, items = knn_recommend_topn(R, w, nbrs, users, n_rec=7,
                                       use_pallas=use_pallas)
    ref = np.asarray(knn_scores_ref(R, w, nbrs, users))
    Rn, un = np.asarray(R), np.asarray(users)
    for b in range(5):
        order = np.argsort(-ref[b], kind="stable")[:7]
        assert np.array_equal(np.asarray(scores[b]), ref[b][order])
        finite = np.isfinite(np.asarray(scores[b]))
        assert np.all(Rn[un[b], np.asarray(items[b])[finite]] == 0)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 70), st.integers(2, 90),
       st.integers(2, 130))
def test_property_similarity_any_shape(seed, nq, n, m):
    rng = np.random.default_rng(seed)
    Q = jnp.asarray(rng.normal(size=(nq, m)).astype(np.float32))
    R = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    out = cosine_similarity(Q, R)
    assert out.shape == (nq, n)
    ref = similarity_ref(Q, R, jnp.linalg.norm(Q, axis=1),
                         jnp.linalg.norm(R, axis=1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 40), st.integers(1, 9))
def test_property_bag_any_shape(seed, nb, hot):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 64, (nb, hot)).astype(np.int32))
    out = embedding_bag(table, idx)
    ref = embedding_bag_ref(table, idx, jnp.ones((nb, hot)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

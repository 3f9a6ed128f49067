"""The rotation's re-sorts against a NumPy stable-sort oracle, bit for bit.

Rotation gates write-region entries out of every base row, re-sorts the
gated lists and merges the burst in (``_merge_base_rows``), and sorts the
recovered burst rows into full-width lists (``_burst_rows``).  Both are
pure data movement, so their outputs must equal a stable NumPy re-sort of
the same entries exactly: values as int32 bit patterns, ids as they are.
The inputs carry what served arenas carry: head sentinels (columns that
were inactive at a row's build), rows refreshed by ``add_rating`` that
already hold write-region entries, and tied similarities, whose order the
oracle fixes (stored order first, then the burst in burst order).

The structural guards keep the permutation inside the sort: a per-element
gather of a whole list is what the sort's payload replaces.  On a TPU v5e
at 8,128 x 8,192 the two such gathers took 3.7 s, and the whole program
without them 0.16 s.
"""
from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.rotation import _burst_rows, _merge_base_rows
from repro.core.types import SENTINEL, SENTINEL_GATE
from repro.kernels.list_merge.ops import _sort_inserts

N_BASE, K = 40, 8
N = N_BASE + K


def _sims(rng, shape):
    """Similarities on a 1/8 grid in [-1, 1], so that many tie; ``+ 0.0``
    turns rounding's -0.0 into 0.0, which NumPy and XLA order alike."""
    return (np.round(rng.uniform(-1.0, 1.0, shape) * 8) / 8 + 0.0
            ).astype(np.float32)


def _arena(rng):
    """(N, N) sorted lists plus the (K, N) recovered burst block.

    Base rows hold head sentinels on the write-region columns, except the
    refreshed rows, which hold real similarities there (as a row re-sorted
    by ``add_rating`` over the live active set does).  Burst row t holds
    sim(u_t, u_s) only for s < t, as onboarding leaves it."""
    raw = _sims(rng, (N, N))
    refreshed = rng.random(N) < 0.25
    refreshed[:2] = (True, False)
    raw[~refreshed, N_BASE:] = SENTINEL
    idx = np.argsort(raw, axis=1, kind="stable").astype(np.int32)
    vals = np.take_along_axis(raw, idx, axis=1)
    U = _sims(rng, (K, N))
    s, t = np.meshgrid(np.arange(K), np.arange(K))
    U[:, N_BASE:][s >= t] = SENTINEL
    return vals, idx, U


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _merge_oracle(vals, idx, U, rows, buf_ids, n_base):
    """Gate, stable re-sort, then merge: a stable sort of (k head pads,
    the re-sorted row, the burst in burst order) minus its k smallest —
    an insert lands after the row entries it ties with, as k sequential
    ``searchsorted(side="right")`` inserts put it."""
    k = U.shape[0]
    out_v, out_i = [], []
    for x in rows:
        gate = idx[x] < n_base
        gv = np.where(gate, vals[x], SENTINEL).astype(np.float32)
        gi = np.where(gate, idx[x], -1).astype(np.int32)
        order = np.argsort(gv, kind="stable")
        cat_v = np.concatenate([np.full(k, SENTINEL, np.float32),
                                gv[order], U[:, x]])
        cat_i = np.concatenate([np.full(k, -1, np.int32), gi[order],
                                buf_ids])
        order = np.argsort(cat_v, kind="stable")[k:]
        out_v.append(cat_v[order])
        out_i.append(cat_i[order])
    return np.stack(out_v), np.stack(out_i)


def _burst_oracle(U, n_base, n_frozen, n_new):
    k = n_frozen - n_base
    C = U[:, n_base:n_frozen]
    C = np.where(C > SENTINEL_GATE, C, C.T)
    np.fill_diagonal(C, 1.0)
    W = np.full((k, n_new), SENTINEL, np.float32)
    W[:, :n_base] = U[:, :n_base]
    W[:, n_base:n_frozen] = C
    bi = np.argsort(W, axis=1, kind="stable").astype(np.int32)
    return np.take_along_axis(W, bi, axis=1), bi


@pytest.mark.parametrize("use_pallas", [False, None, True])
@pytest.mark.parametrize("n_rows", [1, 7, N_BASE])
def test_merge_base_rows_matches_stable_sort_oracle(n_rows, use_pallas):
    rng = np.random.default_rng(1000 + n_rows)
    vals, idx, U = _arena(rng)
    rows = rng.permutation(N_BASE)[:n_rows].astype(np.int32)
    rows[0] = 0                                    # a refreshed row
    buf = np.arange(N_BASE, N, dtype=np.int32)
    mv, mi = _merge_base_rows(jnp.asarray(vals), jnp.asarray(idx),
                              jnp.asarray(U), jnp.asarray(rows),
                              jnp.asarray(buf), n_base=N_BASE,
                              use_pallas=use_pallas)
    ref_v, ref_i = _merge_oracle(vals, idx, U, rows, buf, N_BASE)
    assert mv.shape == (n_rows, N + K) and mi.dtype == jnp.int32
    np.testing.assert_array_equal(_bits(mv), _bits(ref_v))
    np.testing.assert_array_equal(np.asarray(mi), ref_i)
    # Every base and burst id exactly once; the gated write-region
    # entries of refreshed rows are gone, not duplicated.
    for r in range(n_rows):
        real = np.asarray(mi[r])[np.asarray(mv[r]) > SENTINEL_GATE]
        assert sorted(real) == list(range(N))


@pytest.mark.parametrize("extra", [0, 5])
@pytest.mark.parametrize("k", [1, K])
def test_burst_rows_match_stable_sort_oracle(k, extra):
    rng = np.random.default_rng(2000 + 10 * k + extra)
    _, _, U = _arena(rng)
    U = U[:k, :N_BASE + k]
    n_new = N_BASE + k + extra
    bv, bi = _burst_rows(jnp.asarray(U), n_base=N_BASE,
                         n_frozen=N_BASE + k, n_new=n_new)
    ref_v, ref_i = _burst_oracle(U, N_BASE, N_BASE + k, n_new)
    assert bi.dtype == jnp.int32
    np.testing.assert_array_equal(_bits(bv), _bits(ref_v))
    np.testing.assert_array_equal(np.asarray(bi), ref_i)


def test_sort_inserts_matches_stable_sort_oracle():
    rng = np.random.default_rng(3000)
    ins_v = _sims(rng, (6, K))
    ins_i = rng.permutation(6 * K).reshape(6, K).astype(np.int32)
    mask = rng.random((6, K)) < 0.7
    sv, si = _sort_inserts(jnp.asarray(ins_v), jnp.asarray(ins_i),
                           jnp.asarray(mask))
    gated = np.where(mask, ins_v, np.float32(-3.0))
    order = np.argsort(gated, axis=1, kind="stable")
    np.testing.assert_array_equal(
        _bits(sv), _bits(np.take_along_axis(gated, order, axis=1)))
    np.testing.assert_array_equal(np.asarray(si),
                                  np.take_along_axis(ins_i, order, axis=1))


# ---------------------------------------------------------------------------
# Structural guards: no per-element gather of a whole list.
# ---------------------------------------------------------------------------

_GATHER = re.compile(r'"?stablehlo\.gather"?.*?slice_sizes = array<i64: '
                     r'([0-9, ]+)>.*?-> tensor<([0-9x]+)x[a-z0-9]+>')


def _list_gathers(text: str, shape: tuple[int, int]) -> list[tuple]:
    """Slice sizes of every gather whose result has the list shape, up to
    unit dimensions (``take_along_axis`` gathers into ``(b, N, 1)``)."""
    def squeeze(dims):
        return tuple(d for d in dims if d != 1)
    return [tuple(int(s) for s in sizes.split(","))
            for sizes, out in _GATHER.findall(text)
            if squeeze(int(d) for d in out.split("x")) == squeeze(shape)]


def test_merge_base_rows_gathers_whole_rows_only():
    """The base rows are fetched whole (slices of (1, N)); the re-sort
    permutes them inside the sort, so no gather of single elements
    produces a (b, N) list."""
    b, f32, i32 = 24, jnp.float32, jnp.int32
    text = _merge_base_rows.lower(
        jnp.zeros((N, N), f32), jnp.zeros((N, N), i32),
        jnp.zeros((K, N), f32), jnp.zeros((b,), i32),
        jnp.zeros((K,), i32), n_base=N_BASE, use_pallas=False).as_text()
    gathers = _list_gathers(text, (b, N))
    assert gathers, "the row fetch should lower to a gather"
    assert all(s == (1, N) for s in gathers), gathers


@pytest.mark.parametrize("site", ["burst_rows", "sort_inserts"])
def test_no_list_gather_in_the_smaller_sorts(site):
    if site == "burst_rows":
        shape = (K, N + 3)
        text = jax.jit(_burst_rows, static_argnames=(
            "n_base", "n_frozen", "n_new")).lower(
            jnp.zeros((K, N), jnp.float32), n_base=N_BASE, n_frozen=N,
            n_new=N + 3).as_text()
    else:
        shape = (24, K)
        text = jax.jit(_sort_inserts).lower(
            jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.int32),
            jnp.ones(shape, jnp.bool_)).as_text()
    assert _list_gathers(text, shape) == []
    assert "stablehlo.sort" in text

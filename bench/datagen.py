"""Rating matrices and onboarding profiles from a seed, vectorised.

The statistics are those of the program's own synthetic generator
(``repro.data.synthetic.synth_ratings``), copied here so that a change to
the program cannot move the benchmark's inputs:

  * integer 1-5 stars, ``rint(3.5 + user bias + item bias + noise)``
    clipped to [1, 5], with biases ~ N(0, 0.6), N(0, 0.5) and noise
    N(0, 0.7);
  * power-law item popularity, ``rank ** -alpha``;
  * a per-user floor of ``min(min_per_user, n_ratings // n_users)``
    distinct items drawn by popularity without replacement, then the
    remainder spread over (uniform user, item by popularity) pairs.

The program loops over users; here every step works on whole arrays, and
the top-up ends on exactly ``n_ratings`` non-zero entries.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def popularity(n_items: int, alpha: float) -> np.ndarray:
    pop = np.arange(1, n_items + 1, dtype=np.float64) ** -alpha
    return pop / pop.sum()


def _draw_items(rng, cdf: np.ndarray, shape) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(shape), side="right"),
                      cdf.size - 1)


def distinct_items(rng, cdf: np.ndarray, n_rows: int, per_row: int
                   ) -> np.ndarray:
    """(n_rows, per_row) item ids, distinct within each row, each drawn by
    popularity: a repeated draw is drawn again, which is sampling without
    replacement."""
    items = _draw_items(rng, cdf, (n_rows, per_row))
    while True:
        order = np.argsort(items, axis=1, kind="stable")
        srt = np.take_along_axis(items, order, axis=1)
        dup_sorted = np.zeros_like(srt, dtype=bool)
        dup_sorted[:, 1:] = srt[:, 1:] == srt[:, :-1]
        if not dup_sorted.any():
            return items
        dup = np.zeros_like(dup_sorted)
        np.put_along_axis(dup, order, dup_sorted, axis=1)
        items[dup] = _draw_items(rng, cdf, int(dup.sum()))


def _stars(rng, ub: np.ndarray, ib: np.ndarray) -> np.ndarray:
    noise = rng.normal(0.0, 0.7, ub.shape)
    return np.clip(np.rint(3.5 + ub + ib + noise), 1, 5).astype(np.int8)


def synth_ratings(seed: int, n_users: int, n_items: int, n_ratings: int,
                  min_per_user: int = 20, alpha: float = 0.8) -> np.ndarray:
    """Dense (n_users, n_items) int8 ratings, 0 = unrated, with exactly
    ``n_ratings`` non-zero entries."""
    rng = _rng(seed, 0)
    cdf = np.cumsum(popularity(n_items, alpha))
    user_bias = rng.normal(0.0, 0.6, n_users)
    item_bias = item_biases(seed, n_items)
    R = np.zeros((n_users, n_items), np.int8)

    base = min(min_per_user, max(1, n_ratings // n_users))
    items = distinct_items(rng, cdf, n_users, base)
    users = np.repeat(np.arange(n_users), base)
    items = items.ravel()
    R[users, items] = _stars(rng, user_bias[users], item_bias[items])

    flat = R.reshape(-1)
    while (deficit := n_ratings - int(np.count_nonzero(flat))) > 0:
        # Draw more pairs than missing; keep the first ``deficit`` that
        # land on an empty cell, each cell once.
        n = deficit + deficit // 4 + 16
        us = rng.integers(0, n_users, n)
        its = _draw_items(rng, cdf, n)
        cell = us.astype(np.int64) * n_items + its
        _, first = np.unique(cell, return_index=True)
        first = np.sort(first)
        first = first[flat[cell[first]] == 0][:deficit]
        flat[cell[first]] = _stars(rng, user_bias[us[first]],
                                   item_bias[its[first]])
    return R


def item_biases(seed: int, n_items: int) -> np.ndarray:
    """The items' quality biases of the deployment made from ``seed``."""
    return _rng(seed, 1).normal(0.0, 0.5, n_items)


def fresh_profiles(rng: np.random.Generator, n: int, item_bias: np.ndarray,
                   per_profile: int, exclude: np.ndarray,
                   alpha: float = 0.8) -> np.ndarray:
    """(n, n_items) int8 new-user rows with ``per_profile`` ratings each,
    drawn with the same statistics as the base (``item_bias`` is the
    base's), all distinct from each other and from every row of
    ``exclude``."""
    n_items = item_bias.size
    cdf = np.cumsum(popularity(n_items, alpha))
    seen = {row.tobytes() for row in exclude}
    out = np.zeros((n, n_items), np.int8)
    todo = np.arange(n)
    while todo.size:
        items = distinct_items(rng, cdf, todo.size, per_profile)
        ub = np.repeat(rng.normal(0.0, 0.6, todo.size), per_profile)
        rows = np.repeat(todo, per_profile)
        out[todo] = 0
        out[rows, items.ravel()] = _stars(rng, ub, item_bias[items.ravel()])
        again = []
        for u in todo:
            key = out[u].tobytes()
            if key in seen:
                again.append(u)
            else:
                seen.add(key)
        todo = np.asarray(again, np.int64)
    return out

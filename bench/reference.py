"""The plain reference: what every answer of a run should be, from the
inputs alone, and the comparison that decides ``correct``.

Nothing here imports the program or takes anything it made.  Similarities
are exact: ratings are small integers, so the dot products are computed
exactly (float32 sums of integer products stay below 2**24; sparse rows
in float64) and divided by float64 norms.  The semantics follow the
server's contract:

  * a user's sorted list holds the cosine to every user that was live
    when the list was last written, ascending, SENTINEL elsewhere: a base
    row (below the arena's base) lists every base user, itself included;
    a row in the write region lists the users before it;
  * ``add_rating(u, ...)`` rewrites u's rating and re-sorts u's own list
    against the ratings of that moment; other users' entries for u keep
    the value they had (the incremental update refreshes one row);
  * TwinSearch (Algorithm 1 with the static candidate cap): the probes'
    equal ranges of width ``tol`` intersect to Set_0, the ``s_max``
    lowest-indexed members of Set_0 and the write region are verified by
    exact row equality;
  * a read scores with the top-k neighbours of the user's list (self and
    dead slots left out): ``predict`` is the similarity-weighted mean of
    the positive-similarity neighbours that rated the item, ``recommend``
    the top-n unseen items by ``sum(w r) / sum(w [r != 0])``,
    ``w = max(sim, 0)``.

A row whose k-th and (k+1)-th neighbours lie closer than ``AMBIGUOUS``
has no single right neighbour set at float32; it is left out of the
comparison and counted.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

SENTINEL = -2.0
AMBIGUOUS = 1e-4        # neighbour-boundary gap below which a read is unset
EPS = 1e-12             # the scoring kernel's denominator floor


# ---------------------------------------------------------------------------
# Similarity rows
# ---------------------------------------------------------------------------

class Exact:
    """Exact cosines.  ``rows`` compares query rows with a fixed matrix of
    users; ``start``/``set``/``rows_of`` follow a matrix that writes
    change one cell at a time and give users' rows against all of it."""

    def __init__(self):
        self._fixed = None

    def rows(self, Q: np.ndarray, R: np.ndarray) -> np.ndarray:
        """(len(Q), len(R)) float64 cosines of integer rows."""
        if self._fixed is None or self._fixed[0] is not R:
            import scipy.sparse as sp
            S = sp.csr_matrix(R).astype(np.float64)
            nR = np.sqrt(np.asarray(S.multiply(S).sum(axis=1)).ravel())
            self._fixed = (R, S, nR)
        _, S, nR = self._fixed
        dots = np.asarray((S @ Q.astype(np.float64).T).T)
        return dots / np.maximum(_norms(Q)[:, None] * nR[None, :], EPS)

    def start(self, R: np.ndarray) -> None:
        self.Rf = R.astype(np.float32)        # integer sums stay exact
        self.sq = _norms(self.Rf) ** 2

    def set(self, u: int, i: int, v: float) -> None:
        self.Rf[u, i] = v
        self.sq[u] = _norms(self.Rf[u][None])[0] ** 2

    def rows_of(self, users) -> np.ndarray:
        users = np.asarray(users, np.int64)
        out = np.empty((users.size, self.Rf.shape[0]), np.float64)
        for a in range(0, users.size, 1024):
            u = users[a:a + 1024]
            dots = (self.Rf[u] @ self.Rf.T).astype(np.float64)
            out[a:a + 1024] = dots / np.maximum(
                np.sqrt(self.sq[u][:, None] * self.sq[None, :]), EPS)
        return out


def _norms(R: np.ndarray) -> np.ndarray:
    Rf = R.astype(np.float64)
    return np.sqrt(np.einsum("ij,ij->i", Rf, Rf))


# ---------------------------------------------------------------------------
# Lists
# ---------------------------------------------------------------------------

UNIT = 0.999    # a cosine at least this high is a user's own or a twin's


@dataclass
class ListCheck:
    """Widest similarity errors and the number of malformed rows.

    The error is split by the exact cosine: ``unit_err`` where it is
    ``UNIT`` or more (a user's own entry, a twin's), ``sim_err`` below.
    A unit entry sums every rating of a user, so float32 rounds it most;
    the rest of a list shows the precision of the products."""
    sim_err: float = 0.0
    unit_err: float = 0.0
    rows_wrong: int = 0
    notes: list = field(default_factory=list)
    worst: dict = field(default_factory=dict)   # where each was read
    gap_sum: float = 0.0
    gap_n: int = 0

    def _note(self, name: str, gap, cols, vals, want_row, u) -> None:
        if not gap.size:
            return
        at = int(np.argmax(gap))
        if gap[at] > getattr(self, name):
            setattr(self, name, float(gap[at]))
            self.worst[name] = {"row": int(u), "col": int(cols[at]),
                                "got": float(vals[at]),
                                "want": float(want_row[cols[at]])}

    def summary(self) -> dict:
        return {"sim_err": self.sim_err, "unit_err": self.unit_err,
                "mean_gap": self.gap_sum / max(self.gap_n, 1),
                "entries": self.gap_n, "where": self.worst}


def expected_row(sims: np.ndarray, live: int, width: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """A list as the reference writes it: users [0, live) with ``sims``,
    ascending, SENTINEL in the other ``width - live`` slots."""
    vals = np.full(width, SENTINEL, np.float64)
    vals[:live] = sims[:live]
    order = np.argsort(vals, kind="stable")
    return vals[order], order.astype(np.int64)


def check_rows(users: np.ndarray, live: np.ndarray, want: np.ndarray,
               got_vals: np.ndarray, got_idx: np.ndarray,
               got_ratings: np.ndarray | None = None,
               want_ratings: np.ndarray | None = None) -> ListCheck:
    """Rows ``users`` hold every user below ``live`` once with its cosine
    ``want`` (float64), ascending, SENTINEL elsewhere, and (when given)
    the rating row that was written."""
    out = ListCheck()
    for j, u in enumerate(users):
        v, i, n = got_vals[j], got_idx[j], int(live[j])
        is_live = v > -1.5
        bad = []
        if not np.all(np.diff(v) >= 0):
            bad.append("not ascending")
        ids = np.sort(i[is_live])
        if ids.size != n or not np.array_equal(ids, np.arange(n)):
            bad.append(f"lists {ids.size} live ids, not users 0..{n - 1}")
        else:
            cols, vals = i[is_live], v[is_live].astype(np.float64)
            gap = np.abs(vals - want[j, cols])
            unit = want[j, cols] >= UNIT
            out.gap_sum += float(gap.sum())
            out.gap_n += gap.size
            out._note("sim_err", gap[~unit], cols[~unit], vals[~unit],
                      want[j], u)
            out._note("unit_err", gap[unit], cols[unit], vals[unit],
                      want[j], u)
        if not np.all(v[~is_live] == SENTINEL):
            bad.append("a dead slot is not SENTINEL")
        if got_ratings is not None and not np.array_equal(
                got_ratings[j], want_ratings[j]):
            bad.append("rating row differs from the one written")
        if bad:
            out.rows_wrong += 1
            out.notes.append(f"row {int(u)}: " + "; ".join(bad))
    return out


# ---------------------------------------------------------------------------
# TwinSearch decisions
# ---------------------------------------------------------------------------

def twin_decision(twins: np.ndarray, r0: np.ndarray, probes: np.ndarray,
                  probe_rows: np.ndarray, n_base: int, s_max: int,
                  tol: float, probe_sims: np.ndarray) -> bool:
    """Algorithm 1 with the static candidate cap, on exact similarities.
    ``twins``: ids of the live users whose row equals ``r0``;
    ``probe_rows``: the probes' rating rows; ``probe_sims``: (c, n_base),
    each probe's cosine to every base user."""
    if twins.size == 0:
        return False
    if np.any(twins >= n_base):                  # the write region
        return True
    s0 = Exact().rows(r0[None, :], probe_rows)[0]     # sim(u0, probe_i)
    cand = np.all(np.abs(probe_sims[:, :n_base] - s0[:, None]) <= tol,
                  axis=0)
    for p, s in zip(probes, s0):
        if abs(s - 1.0) <= tol:
            cand[p] = True
    gathered = np.flatnonzero(cand)[:s_max]
    return bool(np.isin(twins, gathered).any())


def s_max_for(n_base: int, divisor: int = 125, slack: float = 1.5,
              minimum: int = 8) -> int:
    """The paper's |Set_0| bound n/125 as the static gather size."""
    cap = max(minimum, int(np.ceil(n_base / divisor * slack)))
    return -(-cap // 512) * 512 if cap > 512 else cap


# ---------------------------------------------------------------------------
# Reads
# ---------------------------------------------------------------------------

@dataclass
class ReadCheck:
    rec_err: float = 0.0
    pred_err: float = 0.0
    rec_wrong: int = 0
    ambiguous: int = 0
    compared: int = 0
    notes: list = field(default_factory=list)


def neighbours(sims: np.ndarray, user: int, k: int
               ) -> tuple[np.ndarray, np.ndarray, bool]:
    """The k most similar other users (ids, sims, best first) and whether
    the k-th and (k+1)-th lie too close to order."""
    s = sims.astype(np.float64).copy()
    s[user] = -np.inf
    kk = min(k + 1, s.size)
    part = np.argpartition(-s, kk - 1)[:kk]
    part = part[np.argsort(-s[part], kind="stable")]
    top = part[:k]
    ambiguous = (kk > k and s[top[-1]] > 0
                 and s[top[-1]] - s[part[k]] < AMBIGUOUS)
    return top, s[top], ambiguous


def scores(R: np.ndarray, user: int, nbrs: np.ndarray, sims: np.ndarray
           ) -> np.ndarray:
    r = R[nbrs].astype(np.float64)
    w = np.maximum(sims, 0.0)[:, None]
    s = (w * r).sum(0) / np.maximum((w * (r != 0)).sum(0), EPS)
    s[R[user] != 0] = -np.inf
    return s


def prediction(R: np.ndarray, nbrs: np.ndarray, sims: np.ndarray,
               item: int) -> float:
    r = R[nbrs, item].astype(np.float64)
    w = np.where((r != 0) & (sims > 0), sims, 0.0)
    d = np.abs(w).sum()
    return float((w * r).sum() / d) if d > 0 else 0.0


def compare_recommend(want: np.ndarray, got: list, n: int, out: ReadCheck,
                      tag: str) -> None:
    """``got``: [(item, score)] from the server; ``want``: reference scores
    of every item (seen items at -inf)."""
    items = np.asarray([it for it, _ in got], np.int64)
    vals = np.asarray([s for _, s in got], np.float64)
    n_want = min(n, int(np.isfinite(want).sum()))
    if (len(got) != n or len(set(items.tolist())) != len(items)
            or np.any(items < 0) or np.any(items >= want.size)):
        out.rec_wrong += 1
        out.notes.append(f"{tag}: malformed answer {got[:3]}...")
        return
    finite = np.isfinite(want[items])
    if finite.sum() < n_want:
        out.rec_wrong += 1
        out.notes.append(f"{tag}: recommends an item the user rated")
        return
    err = np.abs(vals[finite] - want[items][finite])
    nth = np.sort(want[np.isfinite(want)])[::-1][n_want - 1]
    shortfall = max(0.0, nth - float(vals[finite].min()))
    out.rec_err = max(out.rec_err, float(err.max(initial=0.0)), shortfall)


# ---------------------------------------------------------------------------
# Limits
# ---------------------------------------------------------------------------

def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that the deployment gives a limit beside its limit;
    correct iff none is over.  A number without a limit is a reading
    only."""
    table = {k: {"value": numbers[k], "limit": lim}
             for k, lim in limits.items() if k in numbers}
    return all(v["value"] <= v["limit"] for v in table.values()), table

"""The one traffic generator: a mix file of parameters in, a seeded
open-loop schedule out.

A mix (``bench/traffic/<name>.json``) gives an arrival process, a rate
and a list of operations with their shares::

    {"arrivals": {"process": "poisson", "rate_per_s": 20.0},
     "ops": [{"op": "recommend", "share": 0.25, "batch": 256, "n": 10,
              "k_neighbors": 20, "users": {"dist": "zipf", "theta": 0.99}},
             ...]}

Every seed gets the same work in another order: the window holds
``round(rate * seconds)`` requests at the same due times for every seed
(exponential quantiles in one fixed order: a Poisson process with that
many arrivals), each operation's count is its share of the total, and a
seed reorders the operations only inside blocks of ``BLOCK`` consecutive
requests; the payloads (which users, items, profiles) are drawn from the
seed.

Operations and their parameters:

  onboard     ``profile``: ``copy_of_base`` (an exact copy of a base user's
              row, the user uniform) or ``fresh`` (a new profile with
              ``ratings_per_profile`` ratings, a number or ``"mean"`` for
              the deployment's mean, distinct from every other row).
  recommend   ``batch`` users from ``users``, ``n`` items each from
              ``k_neighbors`` neighbours.
  predict     ``batch`` (user, item) pairs, users from ``users``, items
              by the deployment's popularity; ``k`` neighbours.
  add_rating  one (user, item, stars) write, the user from ``users``,
              the item by popularity, the stars uniform on 1..5.

``users``: ``{"dist": "uniform"}`` or ``{"dist": "zipf", "theta": t}``
(YCSB's Zipfian over a seeded permutation of the base users).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import datagen

ONBOARD, RECOMMEND, PREDICT, ADD_RATING = ("onboard", "recommend", "predict",
                                           "add_rating")
READS = (RECOMMEND, PREDICT)


@dataclass
class Request:
    due: float                  # seconds after the window opens
    op: str
    args: dict = field(default_factory=dict)


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix["arrivals"]["process"] != "poisson":
        raise ValueError(f"{path}: unknown arrival process "
                         f"{mix['arrivals']['process']!r}")
    for op in mix["ops"]:
        if op["op"] not in (ONBOARD, RECOMMEND, PREDICT, ADD_RATING):
            raise ValueError(f"{path}: unknown op {op['op']!r}")
    return mix


def bucket(n: int) -> int:
    """The power-of-two shape bucket the server pads a batch of n to."""
    return 1 << max(0, n - 1).bit_length()


class _Users:
    def __init__(self, rng, n_users: int, spec: dict):
        self.rng, self.n = rng, n_users
        self.dist = spec.get("dist", "uniform")
        if self.dist == "zipf":
            p = np.arange(1, n_users + 1, dtype=np.float64) ** -float(
                spec["theta"])
            self.cdf = np.cumsum(p / p.sum())
            self.perm = rng.permutation(n_users)
        elif self.dist != "uniform":
            raise ValueError(f"unknown user distribution {self.dist!r}")

    def draw(self, size) -> np.ndarray:
        if self.dist == "uniform":
            return self.rng.integers(0, self.n, size)
        rank = np.minimum(np.searchsorted(self.cdf, self.rng.random(size),
                                          side="right"), self.n - 1)
        return self.perm[rank]


class Generator:
    """Draws payloads for one deployment from one seed."""

    def __init__(self, config: dict, R: np.ndarray, seed: int, stream: int):
        self.config, self.R = config, R
        self.rng = np.random.default_rng([int(seed) % (1 << 63), 100 + stream])
        self.seed, self.stream = seed, stream
        self.cdf = np.cumsum(datagen.popularity(
            config["n_items"], config["popularity_alpha"]))
        self._users: dict[str, _Users] = {}

    def users(self, spec: dict) -> _Users:
        key = json.dumps(spec, sort_keys=True)
        if key not in self._users:
            self._users[key] = _Users(self.rng, self.config["n_users"], spec)
        return self._users[key]

    def items(self, size) -> np.ndarray:
        return np.minimum(np.searchsorted(self.cdf, self.rng.random(size),
                                          side="right"), self.cdf.size - 1)

    def payloads(self, op: dict, count: int) -> list[dict]:
        kind = op["op"]
        if kind == ONBOARD:
            return [{"row": r} for r in self.profiles(op, count)]
        if kind == ADD_RATING:
            users = self.users(op["users"]).draw(count)
            items = self.items(count)
            stars = self.rng.integers(1, 6, count)
            return [{"user": int(u), "item": int(i), "stars": float(s)}
                    for u, i, s in zip(users, items, stars)]
        B = int(op["batch"])
        users = self.users(op["users"]).draw((count, B))
        if kind == RECOMMEND:
            return [{"users": u, "n": int(op["n"]),
                     "k": int(op["k_neighbors"])} for u in users]
        items = self.items((count, B))
        return [{"users": u, "items": it, "k": int(op["k"])}
                for u, it in zip(users, items)]

    def profiles(self, op: dict, count: int) -> np.ndarray:
        if op["profile"] == "copy_of_base":
            return self.R[self.rng.integers(0, self.R.shape[0], count)]
        if op["profile"] != "fresh":
            raise ValueError(f"unknown onboard profile {op['profile']!r}")
        per = op["ratings_per_profile"]
        if per == "mean":
            per = self.config["n_ratings"] // self.config["n_users"]
        return datagen.fresh_profiles(
            self.rng, count, datagen.item_biases(self.seed,
                                                 self.config["n_items"]),
            int(per), self.R, self.config["popularity_alpha"])


def _counts(shares: list[float], n: int) -> list[int]:
    """Largest-remainder split of n by the shares."""
    raw = np.asarray(shares, np.float64) / sum(shares) * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return out.tolist()


BLOCK = 8      # requests whose order a seed may change among themselves


def _interleave(counts: list[int]) -> np.ndarray:
    """Operation indices spread evenly over ``sum(counts)`` slots: each
    operation's j-th request at about ``(j + 1/2) / count`` of the way."""
    keys = [((j + 0.5) / c, i) for i, c in enumerate(counts) for j in range(c)]
    return np.asarray([i for _, i in sorted(keys)], np.int64)


def _blockwise(rng, x: np.ndarray) -> np.ndarray:
    return np.concatenate([rng.permutation(x[a:a + BLOCK])
                           for a in range(0, x.size, BLOCK)])


def schedule(mix: dict, config: dict, R: np.ndarray, seed: int,
             seconds: float) -> list[Request]:
    """The window's requests in due order.  Every seed gets the same
    work at the same times: the arrivals are one fixed sequence of
    exponential gaps (a Poisson process's quantiles in one fixed order),
    the operations one fixed even interleaving that a seed reorders only
    inside consecutive blocks of ``BLOCK`` requests, and the payloads
    (users, items, profiles) are the seed's own.  So a rotation stall, which
    comes after a fixed number of onboards, meets the same queue for
    every seed."""
    rate = float(mix["arrivals"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(0).permutation(-np.log1p(-q))
    rng = np.random.default_rng([int(seed) % (1 << 63), 1])
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / gaps.sum()
    ops = mix["ops"]
    counts = _counts([o["share"] for o in ops], n)
    slots = _blockwise(rng, _interleave(counts))
    gen = Generator(config, R, seed, stream=0)
    payloads = [iter(gen.payloads(op, c)) for op, c in zip(ops, counts)]
    return [Request(float(t), ops[i]["op"], next(payloads[i]))
            for t, i in zip(due, slots)]


def warmup(mix: dict, config: dict, R: np.ndarray, seed: int,
           window: list[Request]) -> list[Request]:
    """Set-up requests: one of each operation the window sends, at each
    batch shape it will use (the server buckets deduplicated batches to
    powers of two, one program per bucket).  Onboards are as many as the
    deployment's health-sweep period, so the sweep runs once too."""
    gen = Generator(config, R, seed, stream=1)
    out: list[Request] = []
    for op in mix["ops"]:
        kind = op["op"]
        if kind in (ONBOARD, ADD_RATING):
            count = (config["server"]["snapshot"]["check_every"]
                     if kind == ONBOARD else 1)
            out += [Request(0.0, kind, p) for p in gen.payloads(op, count)]
            continue
        sizes = sorted({bucket(_n_unique(r)) for r in window if r.op == kind})
        B, n_users = int(op["batch"]), config["n_users"]
        for size in sizes:
            p = gen.payloads(op, 1)[0]
            if kind == RECOMMEND:       # this many distinct users
                cells = gen.rng.choice(n_users, min(size, n_users),
                                       replace=False)
                p["users"] = np.resize(cells, B)
            else:                       # this many distinct (user, item)
                cells = gen.rng.choice(n_users * config["n_items"], size,
                                       replace=False)
                p["users"] = np.resize(cells // config["n_items"], B)
                p["items"] = np.resize(cells % config["n_items"], B)
            out.append(Request(0.0, kind, p))
    # Writes first: the first add_rating builds the server's dot cache.
    return sorted(out, key=lambda r: r.op != ADD_RATING)


def _n_unique(req: Request) -> int:
    if req.op == RECOMMEND:
        return len(np.unique(req.args["users"]))
    return len({(int(u), int(i)) for u, i in zip(req.args["users"],
                                                 req.args["items"])})

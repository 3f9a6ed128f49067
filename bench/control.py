"""The control: the reference put in the server's place, one precision
step below what the deployments state.

The deployments state float32 similarities from matmuls at
``Precision.HIGHEST``, and float32 scores and predictions.  The step
below the first is ``high``: each float32 operand split into a bfloat16
head and a bfloat16 remainder, three one-pass products (head x head,
head x rest, rest x head) summed in float32 — what the TPU runs for
``Precision.HIGH``, written out so that it is the same arithmetic on any
backend.  Like the server's build, it normalises the rows first.  The
step below the second is bfloat16: the reference's score and prediction
arithmetic with every operand and every partial result in bfloat16.  A
run with ``control=True`` writes every compared list from the ``high``
similarities and answers every compared read from them in bfloat16; the
comparison must then fail.

    python3 -m bench.control --workload ml1m.read_zipf --seconds 5 \\
        --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import sys

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def _to_bf16(x):
    """``x`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    and not a round trip through ``astype``: the TPU's compiler may drop
    a float32 -> bfloat16 -> float32 pair as excess precision, which
    would leave the remainder below at zero."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    import jax.numpy as jnp
    hi = _to_bf16(x)
    lo = _to_bf16(x - hi)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _normalise(R):
    import jax.numpy as jnp
    Rf = R.astype(jnp.float32)
    n = jnp.sqrt(jnp.sum(Rf * Rf, axis=1, keepdims=True))
    return Rf / jnp.maximum(n, 1e-12)


def cosine_high(Q, R):
    """(len(Q), len(R)) cosines with bf16_3x products."""
    import jax.numpy as jnp
    qh, ql = _split(_normalise(Q))
    rh, rl = _split(_normalise(R))

    def mm(a, b):
        return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)
    return mm(qh, rh) + mm(qh, rl) + mm(ql, rh)


class Lower:
    """Cosine backend with the interface of ``reference.Exact``, computed
    on the default device at ``high``."""

    def __init__(self):
        import jax
        self._rows = jax.jit(cosine_high)
        self._rows_of = jax.jit(lambda R, u: cosine_high(R[u], R))
        self._set = jax.jit(lambda R, u, i, v: R.at[u, i].set(v))

    def rows(self, Q: np.ndarray, R: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        return np.asarray(self._rows(jnp.asarray(Q, jnp.float32),
                                     jnp.asarray(R, jnp.float32)),
                          np.float64)

    def start(self, R: np.ndarray) -> None:
        import jax.numpy as jnp
        self.R = jnp.asarray(R, jnp.float32)

    def set(self, u: int, i: int, v: float) -> None:
        self.R = self._set(self.R, u, i, v)

    def rows_of(self, users) -> np.ndarray:
        import jax.numpy as jnp
        return np.asarray(self._rows_of(self.R, jnp.asarray(users, jnp.int32)),
                          np.float64)

    # The reference's read arithmetic (``reference.scores`` and
    # ``reference.prediction``) in bfloat16.

    @staticmethod
    def scores(R: np.ndarray, user: int, nbrs: np.ndarray, sims: np.ndarray
               ) -> np.ndarray:
        r = R[nbrs].astype(BF16)
        w = np.maximum(sims, 0.0).astype(BF16)[:, None]
        num = (w * r).sum(0, dtype=BF16)
        den = (w * (r != 0).astype(BF16)).sum(0, dtype=BF16)
        s = (num / np.maximum(den, BF16(1e-12))).astype(np.float64)
        s[R[user] != 0] = -np.inf
        return s

    @staticmethod
    def prediction(R: np.ndarray, nbrs: np.ndarray, sims: np.ndarray,
                   item: int) -> float:
        r = R[nbrs, item].astype(BF16)
        w = np.where((r != 0) & (sims > 0), sims, 0.0).astype(BF16)
        d = np.abs(w).sum(dtype=BF16)
        return float((w * r).sum(dtype=BF16) / d) if d > 0 else 0.0


def main(argv=None) -> int:
    from bench import run
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    for seed in args.seeds:
        try:
            out = run.run_cell(run.ROOT, args.workload, seed, args.seconds,
                               False, control=True)
        except run.NoChip as e:
            print(f"bench.control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": out["result"]["correct"],
                          "checks": out["result"]["checks"],
                          "notes": out["info"]["notes"][:3]
                          + out["info"]["notes"][-3:]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Jit'd public wrapper: pad to block multiples, run the kernel, slice.

The kernel compiles on a TPU and is interpreted elsewhere
(``kernels/platform.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.platform import on_tpu
from repro.kernels.similarity.kernel import similarity_pallas
from repro.kernels.similarity.ref import EPS


def _pad(x: jax.Array, mult: int, axis: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("bq", "bn", "bk"))
def cosine_similarity(Q: jax.Array, R: jax.Array,
                      q_norms: jax.Array | None = None,
                      r_norms: jax.Array | None = None, *,
                      bq: int = 128, bn: int = 256, bk: int = 512
                      ) -> jax.Array:
    """Cosine similarity of each row of Q against each row of R — the
    traditional-path hot loop, on the Pallas kernel."""
    if q_norms is None:
        q_norms = jnp.linalg.norm(Q.astype(jnp.float32), axis=1)
    if r_norms is None:
        r_norms = jnp.linalg.norm(R.astype(jnp.float32), axis=1)
    nq, n = Q.shape[0], R.shape[0]
    Qp = _pad(_pad(Q, bq, 0), bk, 1)
    Rp = _pad(_pad(R, bn, 0), bk, 1)
    qn = jnp.maximum(_pad(q_norms.astype(jnp.float32), bq, 0), EPS)
    rn = jnp.maximum(_pad(r_norms.astype(jnp.float32), bn, 0), EPS)
    out = similarity_pallas(Qp, Rp, qn, rn, bq=bq, bn=bn, bk=bk,
                            interpret=not on_tpu())
    return out[:nq, :n]

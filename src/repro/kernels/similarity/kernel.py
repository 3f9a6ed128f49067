"""Pallas TPU kernel: blocked cosine-similarity matmul with fused
normalisation epilogue.

This is the paper's measured hot spot: the traditional new-user path
computes sim(u0, x) for all n users over m items — a (nq, m) x (m, n)
matmul — and the full build is the (n, m) x (m, n) case.  The kernel tiles
(bq, bn, bk) blocks into VMEM, accumulates fp32 partial dot products on the
MXU over the item (k) grid axis, and divides by the cached row norms in the
epilogue of the final k step — the normalisation never touches HBM as a
separate pass.

Block shapes default to MXU-aligned multiples of 128; the (bq, bk) + (bn,
bk) + (bq, bn) working set at the defaults is ~0.8 MB, comfortably inside
the ~16 MB VMEM budget with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-12


def _sim_kernel(qn_ref, rn_ref, q_ref, r_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        q_ref[...], r_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        denom = jnp.maximum(qn_ref[...] * rn_ref[...], EPS)   # (bq, bn)
        o_ref[...] = acc_ref[...] / denom


def similarity_pallas(Q: jax.Array, R: jax.Array, q_norms: jax.Array,
                      r_norms: jax.Array, *, bq: int = 128, bn: int = 256,
                      bk: int = 512, interpret: bool) -> jax.Array:
    """(nq, m), (n, m) -> (nq, n) cosine similarity, fp32.

    Dimensions must be pre-padded to the block multiples (``ops.py`` does
    this); zero-padded rows produce sim 0 via the EPS-guarded denominator.
    The norms travel as a (nq, 1) column and a (1, n) row: 1-D norm
    blocks get an XLA layout that Mosaic refuses.
    """
    nq, m = Q.shape
    n, m2 = R.shape
    assert m == m2 and nq % bq == 0 and n % bn == 0 and m % bk == 0, (
        Q.shape, R.shape, (bq, bn, bk))
    nk = m // bk
    grid = (nq // bq, n // bn, nk)

    kernel = functools.partial(_sim_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_norms.reshape(nq, 1), r_norms.reshape(1, n), Q, R)

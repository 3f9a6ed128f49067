"""Pallas TPU kernel: fused masked row-equality verification.

Algorithm 1 lines 10-15: test each gathered candidate row against the new
user's rating vector.  The kernel streams (bs, bk) blocks of the candidate
matrix through VMEM, AND-reduces equality per row across the item grid axis
in an int32 scratch accumulator (TPU-friendly lane layout), and applies the
candidate-validity mask in the epilogue.  Bandwidth-bound by design — the
paper's O(|Set_0|·m) term — so the win over the jnp oracle on real hardware
is the fusion (one pass, no (s, m) bool intermediate in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _verify_kernel(c_ref, r0_ref, valid_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.ones_like(acc_ref)

    eq_blk = (c_ref[...] == r0_ref[...][None, :]).all(axis=1)
    acc_ref[...] &= eq_blk[:, None]

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...] & (valid_ref[...][:, None])


def verify_rows_pallas(C: jax.Array, r0: jax.Array, valid: jax.Array, *,
                       bs: int = 256, bk: int = 512,
                       interpret: bool) -> jax.Array:
    """C: (s, m) candidate rows; r0: (m,); valid: (s,) bool.
    Returns (s, 1) bool — row i equals r0 and is a live candidate."""
    s, m = C.shape
    assert s % bs == 0 and m % bk == 0, (C.shape, (bs, bk))
    nk = m // bk
    kernel = functools.partial(_verify_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(s // bs, nk),
        in_specs=[
            pl.BlockSpec((bs, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk,), lambda i, k: (k,)),
            pl.BlockSpec((bs,), lambda i, k: (i,)),
        ],
        out_specs=pl.BlockSpec((bs, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, 1), jnp.bool_),
        scratch_shapes=[pltpu.VMEM((bs, 1), jnp.bool_)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(C, r0, valid)

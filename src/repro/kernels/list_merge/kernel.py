"""Pallas TPU kernel: fused k-way merge-insert for ascending sorted lists.

One grid step owns a (br, LP) block of rows and merges each row's k
pre-sorted inserts in a single pass, replacing k sequential shift-gathers
(k full HBM round-trips of the (N, N) arena) with one read + one write:

  1. insert ranks:   rank_t = |{j : row[j] <= s_t}| + t — one broadcast
                     compare-reduce per insert (the k-way ``searchsorted``);
  2. merge path:     b(j) = |{t : rank_t < j + k}| counts inserts landing
                     strictly before output slot j (merged rank j + k, the
                     k smallest being dropped);
  3. gather:         out[j] = row[j + k − b(j)] or, when an insert's rank
                     equals j + k, ins[b(j)].  The data-dependent offset
                     k − b(j) ∈ [0, k] is resolved as k + 1 selects
                     against the row rotated one lane further each time,
                     so the kernel needs no in-VMEM gather.

Work per row is O(L·k) compares/selects on the VPU, all on (br, LP)
blocks; the inputs stream HBM -> VMEM once, totalling O(N·(N + k)) for the
whole arena versus the sequential path's k·O(N²).

Inputs must be pre-conditioned by ``ops.py``: inserts sorted ascending per
row with masked/padded lanes at ``NEG_INF``, list columns padded to LP >=
L + k with ``POS_INF`` (see ``ref.py`` for the value contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.list_merge.ref import POS_INF

# The kernel keeps about sixteen (br, LP) 32-bit blocks in VMEM, ~0.5 KiB
# per list column at br=8.  Mosaic's default 16 MiB scope stops that near
# L = 30k; with half of a v5e core's 128 MiB it compiles at k = 64 up to
# L = 122,000 (not 125,000), past any dense (N, N) arena that fits the
# chip's 16 GB of HBM.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _merge_kernel(vals_ref, idx_ref, iv_ref, ii_ref, ov_ref, oi_ref, *,
                  kp: int):
    v = vals_ref[...]                                # (br, LP), pad POS_INF
    ids = idx_ref[...]                               # (br, LP) int32
    sv = iv_ref[...]                                 # (br, kp) ascending
    si = ii_ref[...]                                 # (br, kp) int32
    br, LP = v.shape

    # 1+2. insert ranks and merge path.  rank_t = #{row entries <= s_t} + t:
    # row entries tie-break before inserts (side="right"), among equal
    # inserts the +t term preserves burst order, POS_INF pads never count.
    # Output slot j holds merged rank j + kp (first kp dropped); b(j)
    # inserts precede it, and it IS insert b(j) iff some rank_t == j + kp
    # (ranks are strictly increasing in t).
    #
    # All three loops are ``fori_loop``s over (br, LP) carries, so VMEM
    # holds a fixed handful of blocks whatever kp is.  Column t of the
    # inserts is taken with a masked sum (one value plus exact zeros).
    tgt = jax.lax.broadcasted_iota(jnp.int32, (br, LP), 1) + kp
    lane = jax.lax.broadcasted_iota(jnp.int32, (br, kp), 1)

    def column(x, t):
        return jnp.sum(jnp.where(lane == t, x, jnp.zeros_like(x)), axis=1,
                       keepdims=True)                # (br, 1)

    def count(t, carry):
        b, hit = carry
        rank = jnp.sum((v <= column(sv, t)).astype(jnp.int32), axis=1,
                       keepdims=True) + t            # (br, 1)
        return (b + (rank < tgt).astype(jnp.int32),
                hit + (rank == tgt).astype(jnp.int32))

    zeros = jnp.zeros((br, LP), jnp.int32)
    b, hit = jax.lax.fori_loop(0, kp, count, (zeros, zeros))
    is_ins = hit > 0

    # 3. gather: the row part of slot j reads row[j + d] with d = kp - b(j).
    # Walk d = 0..kp with one lane rotation per step; wrap-around lanes are
    # never selected, since j + d < LP wherever b(j) = kp - d.
    def shift(d, carry):
        rv, ri, out_v, out_i = carry
        sel = jnp.logical_not(is_ins) & (b == kp - d)
        return (pltpu.roll(rv, LP - 1, 1),           # rv[:, j] <- rv[:, j+1]
                pltpu.roll(ri, LP - 1, 1),
                jnp.where(sel, rv, out_v), jnp.where(sel, ri, out_i))

    _, _, out_v, out_i = jax.lax.fori_loop(
        0, kp + 1, shift,
        (v, ids, jnp.zeros((br, LP), v.dtype), jnp.zeros((br, LP),
                                                         ids.dtype)))

    def place(t, carry):
        out_v, out_i = carry
        sel = is_ins & (b == t)
        return (jnp.where(sel, column(sv, t), out_v),
                jnp.where(sel, column(si, t), out_i))

    out_v, out_i = jax.lax.fori_loop(0, kp, place, (out_v, out_i))
    ov_ref[...] = out_v
    oi_ref[...] = out_i


def merge_insert_pallas(vals: jax.Array, idx: jax.Array,
                        ins_vals: jax.Array, ins_idx: jax.Array, *,
                        br: int = 8, interpret: bool
                        ) -> tuple[jax.Array, jax.Array]:
    """(R, LP) padded lists + (R, kp) sorted gated inserts -> merged (R, LP).

    ``ops.py`` handles padding (rows to br, columns to LP >= L + kp with
    POS_INF, insert lanes to kp with NEG_INF) and slices the result back.
    Only the leading L output columns are meaningful.
    """
    R, LP = vals.shape
    R2, kp = ins_vals.shape
    assert R == R2 and R % br == 0, (vals.shape, ins_vals.shape, br)
    assert idx.shape == (R, LP) and ins_idx.shape == (R, kp)
    grid = (R // br,)
    kernel = functools.partial(_merge_kernel, kp=kp)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, LP), lambda i: (i, 0)),
            pl.BlockSpec((br, LP), lambda i: (i, 0)),
            pl.BlockSpec((br, kp), lambda i: (i, 0)),
            pl.BlockSpec((br, kp), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, LP), lambda i: (i, 0)),
            pl.BlockSpec((br, LP), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((R, LP), vals.dtype),
            jax.ShapeDtypeStruct((R, LP), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(vals, idx, ins_vals, ins_idx)

"""Pallas TPU kernel: fused batched kNN recommendation scoring.

The serving read path's hot loop: for each (user, neighbour-list) row,
score every catalogue item by the positive-weighted average of the
neighbours' ratings, then mask already-seen items.  The einsum reference
first gathers a (B, k, m) neighbour-ratings block from HBM; at serving
scale (B=256, k=50, m=10^5) that intermediate alone is tens of GB.  Here
the gather never materialises: neighbour ids and weights ride in scalar
memory (``PrefetchScalarGridSpec``) and drive hand-issued DMAs from the
ratings array, which stays in HBM (``memory_space=pl.ANY``).

Grid is (B // 8, mp // bm): one step owns 8 users (a full sublane tile, so
every vector op runs on whole (8, bm) vregs) and one item tile.  HBM keeps
the ratings in (8, 128) tiles and a DMA moves whole tiles, so neighbour
row n arrives inside its aligned block of rows [8 (n // 8), 8 (n // 8) + 8)
and is picked out in VMEM.  The step loops over the k neighbour slots with
the blocks of slot t + 1 in flight while slot t accumulates (two VMEM
slots, one DMA semaphore each); score and rated count accumulate in slot
order, the epilogue normalises and applies the seen-item mask (the users'
own rows, gathered by the wrapper), and the (8, bm) output block is
written once.

Weight contract matches ``ref.py``: weights are pre-clamped ``>= 0`` and
a zero weight (SENTINEL / padded neighbour slot) is an exact no-op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.knn_score.ref import EPS

TILE = 8         # f32 sublanes per HBM tile; also users per grid step


def _score_kernel(nbr_ref, w_ref, r_hbm, seen_ref, o_ref, buf, sem, *,
                  k: int, bm: int):
    row0 = pl.program_id(0) * TILE
    col = pl.multiple_of(pl.program_id(1) * bm, bm)
    sub = jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)

    def copies(t, slot):
        return [pltpu.make_async_copy(
            r_hbm.at[pl.ds(pl.multiple_of(
                nbr_ref[row0 + i, t] // TILE * TILE, TILE), TILE),
                pl.ds(col, bm)],
            buf.at[slot, i], sem.at[slot]) for i in range(TILE)]

    for cp in copies(0, 0):
        cp.start()

    def step(t, carry):
        ssum, dsum = carry
        slot = t % 2

        @pl.when(t + 1 < k)
        def _prefetch():
            for cp in copies(t + 1, 1 - slot):
                cp.start()

        for cp in copies(t, slot):
            cp.wait()
        # Each user's neighbour row out of its block, its weight out of
        # scalar memory: selects and sums with exact zeros only, so r and
        # wt are bit-identical to the gathered rows and to ``w``.
        r = jnp.zeros((TILE, bm), jnp.float32)
        wt = jnp.zeros((TILE, 1), jnp.float32)
        for i in range(TILE):
            hit = sub == nbr_ref[row0 + i, t] % TILE
            row = jnp.sum(jnp.where(hit, buf[slot, i], 0.0), axis=0,
                          keepdims=True)
            r = jnp.where(sub == i, row, r)
            wt = jnp.where(sub == i, w_ref[row0 + i, t], wt)
        ssum = ssum + wt * r
        dsum = dsum + wt * (r != 0).astype(jnp.float32)
        return ssum, dsum

    zero = jnp.zeros((TILE, bm), jnp.float32)
    ssum, dsum = jax.lax.fori_loop(0, k, step, (zero, zero))
    scores = ssum / jnp.maximum(dsum, EPS)
    o_ref[...] = jnp.where(seen_ref[...] != 0, -jnp.inf, scores)


def knn_scores_pallas(ratings: jax.Array, w: jax.Array, nbrs: jax.Array,
                      seen: jax.Array, *, bm: int = 512,
                      interpret: bool) -> jax.Array:
    """ratings: (N, mp) with N % 8 == 0 and mp % bm == 0; w: (B, k) f32
    >= 0 with B % 8 == 0; nbrs: (B, k) int32 in [0, N); seen: (B, mp), the
    querying users' own rating rows.  Returns (B, mp) scores with each
    user's rated items at -inf (see ``ref.py``)."""
    B, k = w.shape
    N, mp = ratings.shape
    assert N % TILE == 0 and B % TILE == 0 and mp % bm == 0, (
        ratings.shape, w.shape, bm)
    assert nbrs.shape == (B, k) and seen.shape == (B, mp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // TILE, mp // bm),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((TILE, bm), lambda g, j, nbr_ref, w_ref: (g, j)),
        ],
        out_specs=pl.BlockSpec((TILE, bm), lambda g, j, nbr_ref, w_ref:
                               (g, j)),
        scratch_shapes=[
            pltpu.VMEM((2, TILE, TILE, bm), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_score_kernel, k=k, bm=bm)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, mp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(nbrs, w, ratings, seen)

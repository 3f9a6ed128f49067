"""Jit'd public wrapper for the k-way merge-insert kernel family.

Pre-conditions the inputs (per-row stable sort of the gated inserts,
NEG_INF gating of masked lanes, POS_INF column padding) and routes to one
of two equivalent backends:

  * ``use_pallas=True``  — the fused Pallas kernel (``kernel.py``;
    compiled on a TPU, interpreted elsewhere, see ``kernels/platform.py``);
  * ``use_pallas=False`` — a pure-XLA merge: two ``searchsorted`` rank
    computations plus one scatter, O(R·(L + k)) data movement.

``use_pallas=None`` (default) picks the Pallas kernel on TPU backends and
the XLA merge elsewhere.  Both are asserted element-identical to the
``ref.py`` oracle (and hence to k sequential inserts) in the tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.list_merge.kernel import merge_insert_pallas
from repro.kernels.list_merge.ref import NEG_INF, POS_INF
from repro.kernels.platform import on_tpu


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _sort_inserts(ins_vals: jax.Array, ins_idx: jax.Array,
                  ins_mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gate masked lanes to NEG_INF and stable-sort each row's inserts
    ascending — ties keep burst order, masked lanes sort to the front.
    The indices ride the sort as its payload (no argsort + gathers)."""
    gated = jnp.where(ins_mask, ins_vals, NEG_INF)
    return lax.sort((gated, ins_idx), dimension=1, is_stable=True,
                    num_keys=1)


def _merge_xla(vals: jax.Array, idx: jax.Array, sv: jax.Array,
               si: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Rank-and-scatter merge (no Pallas): the true O(R·(L + k)) path.

    Merged rank of insert t: #{row <= s_t} (side="right": equal row
    entries are older) + t; of row entry j: j + #{inserts < row[j]}
    (side="left": equal inserts are younger).  Ranks form a permutation of
    0..L+k-1; entries with rank >= k survive at output slot rank - k, the
    rest scatter to slot L and are dropped.
    """
    R, L = vals.shape
    k = sv.shape[1]
    p = jax.vmap(lambda row, s: jnp.searchsorted(row, s, side="right"))(
        vals, sv).astype(jnp.int32)
    rank_ins = p + jnp.arange(k, dtype=jnp.int32)[None, :]
    c = jax.vmap(lambda s, row: jnp.searchsorted(s, row, side="left"))(
        sv, vals).astype(jnp.int32)
    rank_row = jnp.arange(L, dtype=jnp.int32)[None, :] + c

    rows = jnp.arange(R, dtype=jnp.int32)[:, None]
    t_row = jnp.where(rank_row >= k, rank_row - k, L)    # L -> dropped
    t_ins = jnp.where(rank_ins >= k, rank_ins - k, L)
    out_v = jnp.zeros_like(vals).at[rows, t_row].set(vals, mode="drop")
    out_i = jnp.zeros_like(idx).at[rows, t_row].set(idx, mode="drop")
    out_v = out_v.at[rows, t_ins].set(sv.astype(vals.dtype), mode="drop")
    out_i = out_i.at[rows, t_ins].set(si.astype(idx.dtype), mode="drop")
    return out_v, out_i


@partial(jax.jit, static_argnames=("use_pallas", "br"))
def merge_insert(vals: jax.Array, idx: jax.Array, ins_vals: jax.Array,
                 ins_idx: jax.Array, ins_mask: jax.Array | None = None, *,
                 use_pallas: bool | None = None, br: int = 8
                 ) -> tuple[jax.Array, jax.Array]:
    """Merge k (value, index) inserts into each of R ascending lists.

    Args:
      vals:     (R, L) float32 ascending per row, values in
                (NEG_INF, POS_INF).
      idx:      (R, L) int32 companion indices.
      ins_vals: (R, k) insert values in burst order (k-th axis).
      ins_idx:  (k,) or (R, k) int32 insert indices.
      ins_mask: optional (R, k) bool; False lanes are exact no-ops for
                that row.

    Returns (vals', idx') of shape (R, L): the merged lists with the k
    smallest merged elements dropped — element-identical to k sequential
    drop-min ``searchsorted(side="right")`` shift-inserts in burst order.
    """
    R, L = vals.shape
    k = ins_vals.shape[-1]
    vals = vals.astype(jnp.float32)
    idx = idx.astype(jnp.int32)
    ins_vals = jnp.broadcast_to(ins_vals.astype(jnp.float32), (R, k))
    ins_idx = jnp.broadcast_to(ins_idx.astype(jnp.int32), (R, k))
    if ins_mask is None:
        ins_mask = jnp.ones((R, k), jnp.bool_)
    else:
        ins_mask = jnp.broadcast_to(ins_mask, (R, k))

    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        sv, si = _sort_inserts(ins_vals, ins_idx, ins_mask)
        return _merge_xla(vals, idx, sv, si)

    # Pallas path: pad insert lanes BEFORE the sort (NEG_INF lanes self-
    # drop and must not trail the ascending order, see ref.py), rows to
    # the block multiple, columns to LP >= L + kp on a lane boundary.
    # Padded rows/columns are sliced away below.
    kp = max(8, _round_up(k, 8))
    Rp = _round_up(R, br)
    LP = _round_up(L + kp, 128)
    ins_vals = jnp.pad(ins_vals, ((0, Rp - R), (0, kp - k)))
    ins_idx = jnp.pad(ins_idx, ((0, Rp - R), (0, kp - k)))
    ins_mask = jnp.pad(ins_mask, ((0, Rp - R), (0, kp - k)))
    sv, si = _sort_inserts(ins_vals, ins_idx, ins_mask)
    vp = jnp.pad(vals, ((0, Rp - R), (0, LP - L)),
                 constant_values=float(POS_INF))
    ip = jnp.pad(idx, ((0, Rp - R), (0, LP - L)))
    out_v, out_i = merge_insert_pallas(vp, ip, sv, si, br=br,
                                       interpret=not on_tpu())
    return out_v[:R, :L], out_i[:R, :L]

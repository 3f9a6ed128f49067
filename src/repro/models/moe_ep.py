"""Expert-parallel MoE under ``shard_map`` — explicit all-to-all dispatch.

The GSPMD-constraint formulation in ``moe.py`` is portable (and the §Perf
baseline), but the partitioner materialises replicated activation-sized
gradients around the dispatch scatter (measured ~95GB/device of all-reduce
per layer on llama4-scout).  This module is the production path: the token
<-> expert exchange is written as the textbook pair of ``all_to_all``s over
the model axis, with FSDP weight shards explicitly ``all_gather``ed (and
reduce-scattered in the backward, via the all_gather transpose):

  tokens (sharded dp x mp)  --a2a-->  expert rows (E/mp experts per shard)
        expert GEMMs (full f, weights gathered over dp)
  expert rows  --a2a-->  tokens, combine with gates

Per-device traffic: 2 x T_loc·k·cf·d activation bytes over the model axis +
one weight gather over dp per layer — the intrinsic MoE cost.

Semantics match ``moe.py`` exactly when nothing overflows capacity (same
per-token expert dot products); capacity accounting is per *local* shard,
which is the standard EP formulation.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig


class MoEEPInfo(NamedTuple):
    """Static routing info the sharding layer hands the model."""

    dp: tuple[str, ...]          # data axes (token sharding / weight FSDP)
    mp: str                      # model axis (expert sharding / all-to-all)
    mp_size: int
    win_spec: object             # P of the sliced (E, d, gf·f) w_in
    wout_spec: object            # P of the sliced (E, f, d) w_out
    acts_spec: object            # P of the (B, S, d) activations
    mesh: object = None          # concrete Mesh (bound at cell build)


def _gather_axes(spec) -> tuple:
    """dp axes on the last dim of a weight spec (() = not FSDP-sharded)."""
    last = tuple(spec)[-1] if len(tuple(spec)) else None
    if last is None:
        return ()
    return last if isinstance(last, tuple) else (last,)


def moe_ffn_ep(x: jax.Array, router_w: jax.Array, w_in: jax.Array,
               w_out: jax.Array, cfg: MoEConfig, act: str,
               info: MoEEPInfo) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) sharded ``info.acts_spec`` -> (out, aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    mp_n = info.mp_size
    assert E % mp_n == 0, (E, mp_n)
    E_loc = E // mp_n
    glu = act in ("swiglu", "geglu")
    win_gather = _gather_axes(info.win_spec)
    wout_gather = _gather_axes(info.wout_spec)

    def local_fn(x_loc, rw, w_in_loc, w_out_loc):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        xt = x_loc.reshape(T, d)
        logits = jnp.einsum("td,de->te", xt, rw.astype(xt.dtype),
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, k)            # (T, k)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

        C = max(8, -(-int(T * k * cfg.capacity_factor / E) // 8) * 8)
        flat_e = eidx.reshape(-1)                        # (T·k,)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
        valid = pos < C
        slot = flat_e * C + jnp.minimum(pos, C - 1)      # [0, E·C)

        xk = jnp.repeat(xt, k, axis=0) * valid[:, None].astype(xt.dtype)
        send = jnp.zeros((E * C, d), xt.dtype).at[slot].add(xk)

        # ---- dispatch all-to-all over the model axis ----
        recv = jax.lax.all_to_all(send, info.mp, split_axis=0,
                                  concat_axis=0, tiled=True)
        # (mp·E_loc·C, d): peer-major blocks of my local experts' rows.
        recv = recv.reshape(mp_n, E_loc, C, d).transpose(1, 0, 2, 3)
        recv = recv.reshape(E_loc, mp_n * C, d)

        # ---- expert GEMMs (FSDP weight shards gathered over dp) ----
        w_in_full = (jax.lax.all_gather(w_in_loc, win_gather, axis=2,
                                        tiled=True)
                     if win_gather else w_in_loc)        # (E_loc, d, gf·f)
        w_out_full = (jax.lax.all_gather(w_out_loc, wout_gather, axis=2,
                                         tiled=True)
                      if wout_gather else w_out_loc)     # (E_loc, f, d)
        h = jnp.einsum("ecd,edf->ecf", recv, w_in_full.astype(recv.dtype))
        if glu:
            g, u = jnp.split(h, 2, axis=-1)
            inner = {"swiglu": jax.nn.silu,
                     "geglu": lambda v: jax.nn.gelu(v, approximate=True)}[
                         act](g) * u
        else:
            inner = jax.nn.gelu(h)
        out = jnp.einsum("ecf,efd->ecd", inner,
                         w_out_full.astype(inner.dtype))

        # ---- combine all-to-all (reverse of dispatch) ----
        back = out.reshape(E_loc, mp_n, C, d).transpose(1, 0, 2, 3)
        back = back.reshape(E * C, d)
        ret = jax.lax.all_to_all(back, info.mp, split_axis=0,
                                 concat_axis=0, tiled=True)
        yk = ret[slot] * (gates.reshape(-1) *
                          valid.astype(jnp.float32)).astype(
            ret.dtype)[:, None]
        y = jnp.sum(yk.reshape(T, k, d), axis=1).reshape(Bl, Sl, d)

        # ---- global load-balance aux (Switch) ----
        all_axes = info.dp + (info.mp,)
        frac = jax.lax.psum(jnp.sum(onehot.astype(jnp.float32), axis=0),
                            all_axes)
        prob = jax.lax.psum(jnp.sum(probs, axis=0), all_axes)
        t_tot = jax.lax.psum(jnp.float32(T), all_axes)
        aux = E * jnp.sum((frac / (k * t_tot)) * (prob / t_tot))
        return y, aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=info.mesh,
        in_specs=(info.acts_spec, P(None, None), info.win_spec,
                  info.wout_spec),
        out_specs=(info.acts_spec, P()),
        check_vma=False,
    )(x, router_w, w_in, w_out)
    return y, aux

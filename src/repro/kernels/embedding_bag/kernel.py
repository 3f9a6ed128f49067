"""Pallas TPU kernel: EmbeddingBag via scalar-prefetch-driven row gather.

The recsys substrate's hot path: sum (or weighted-sum) of ``hot`` embedding
rows per bag from a large table.  The classic TPU pattern: bag indices ride
in scalar memory (``PrefetchScalarGridSpec``) and *drive the BlockSpec
index_map*, so each grid step DMAs exactly the (1, dim) table row it needs
from HBM — the gather never materialises an (n_bags·hot, dim) intermediate.
Accumulation happens in the revisited output block across the ``hot`` grid
axis (h == 0 initialises).

Weights fold in the multi-hot validity mask (0.0 = padding slot), matching
``torch.nn.EmbeddingBag(mode='sum', per_sample_weights=...)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, w_ref, table_ref, o_ref):
    b = pl.program_id(0)
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[b, h]
    o_ref[...] += table_ref[...] * w


def embedding_bag_pallas(table: jax.Array, idx: jax.Array,
                         weights: jax.Array, *,
                         interpret: bool) -> jax.Array:
    """table: (V, dim); idx: (n_bags, hot) int32; weights: (n_bags, hot)
    f32 (0 for padding slots).  Returns (n_bags, dim) weighted bag sums."""
    n_bags, hot = idx.shape
    V, dim = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_bags, hot),
        in_specs=[
            pl.BlockSpec((1, dim), lambda b, h, idx_ref, w_ref:
                         (idx_ref[b, h], 0)),
        ],
        out_specs=pl.BlockSpec((1, dim), lambda b, h, idx_ref, w_ref:
                               (b, 0)),
    )
    return pl.pallas_call(
        _bag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_bags, dim), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(idx, weights, table)

"""Jit'd wrapper for the EmbeddingBag kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.platform import on_tpu


@jax.jit
def embedding_bag(table: jax.Array, idx: jax.Array,
                  weights: jax.Array | None = None,
                  mask: jax.Array | None = None) -> jax.Array:
    """Sum-combiner EmbeddingBag: (V, dim) table, (n_bags, hot) indices,
    optional per-sample weights and validity mask -> (n_bags, dim)."""
    n_bags, hot = idx.shape
    if weights is None:
        weights = jnp.ones((n_bags, hot), jnp.float32)
    if mask is not None:
        weights = weights * mask.astype(weights.dtype)
    idx = jnp.clip(idx.astype(jnp.int32), 0, table.shape[0] - 1)
    return embedding_bag_pallas(table, idx, weights.astype(jnp.float32),
                                interpret=not on_tpu())

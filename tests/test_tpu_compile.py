"""Compile the served Pallas kernels for a TPU v5e at MovieLens-1M size.

No chip is needed: the TPU compiler that ships with jaxlib compiles for a
described v5e topology, so Mosaic refuses here what it would refuse on
the chip (misaligned blocks, VMEM over its limit, unsupported layouts).
Interpret-mode tests cannot see any of that.  Each test asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep all chip compiles in this one file.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.knn_score.kernel import knn_scores_pallas
from repro.kernels.list_merge.kernel import merge_insert_pallas
from repro.kernels.similarity.kernel import similarity_pallas

# MovieLens-1M: 6,040 users + 64 write slots; 3,706 items padded to 4,096.
N_ARENA, M_PAD = 6104, 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 — skip, not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_knn_scores_compiles_for_v5e(one_chip):
    B, k = 256, 20
    text = _compiled_text(
        lambda r, w, n, s: knn_scores_pallas(r, w, n, s, interpret=False),
        one_chip, ((N_ARENA, M_PAD), jnp.float32), ((B, k), jnp.float32),
        ((B, k), jnp.int32), ((B, M_PAD), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("L", [6104, 1007])
def test_merge_insert_compiles_for_v5e(one_chip, L):
    """The rotation merges capacity_extra = 64 inserts into every base row
    of the arena; L = 6,104 is the MovieLens-1M arena width."""
    R, k = 64, 64
    LP = -(-(L + k) // 128) * 128
    text = _compiled_text(
        lambda v, i, sv, si: merge_insert_pallas(v, i, sv, si,
                                                 interpret=False),
        one_chip, ((R, LP), jnp.float32), ((R, LP), jnp.int32),
        ((R, k), jnp.float32), ((R, k), jnp.int32))
    assert "tpu_custom_call" in text


def test_similarity_compiles_for_v5e(one_chip):
    nq, n = 128, 6144
    text = _compiled_text(
        lambda q, r, qn, rn: similarity_pallas(q, r, qn, rn,
                                               interpret=False),
        one_chip, ((nq, M_PAD), jnp.float32), ((n, M_PAD), jnp.float32),
        ((nq,), jnp.float32), ((n,), jnp.float32))
    assert "tpu_custom_call" in text

"""Fixtures of the benchmark's CPU tests (helpers in ``benchtools``)."""
from __future__ import annotations

from pathlib import Path

import pytest

from benchtools import MIXES, tiny_config, tiny_durable_config, write_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root")
    return write_root(root, {"tiny": tiny_config(),
                             "tiny_durable": tiny_durable_config()},
                      [("tiny", m) for m in MIXES.values()]
                      + [("tiny_durable", "twin_burst_12")])


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory) -> Path:
    return tmp_path_factory.mktemp("jax_cache")

"""A harness run of the incremental-rotation deployment at a tiny size on
the CPU: the cell is correct against the plain reference, its control is
not, and the rotation layer's readers (``rotation_pause_ms``,
``rotation_step_ms``, ``forced_drains``) find what they read, in that
deployment and in the synchronous one beside it."""
from __future__ import annotations

from pathlib import Path

import pytest

from benchtools import run_tiny, tiny_config, write_root

CELL = "tiny_incremental.twin_burst_12"


def tiny_incremental_config() -> dict:
    """The tiny deployment rotating in slices of 48 base rows an onboard:
    with 8 write slots the reserve is 2, and two slices cover the largest
    base a run reaches (below 96 users), so no plan is drained."""
    cfg = tiny_config()
    cfg["name"] = "tiny_incremental"
    cfg["server"]["rotation"]["budget_rows"] = 48
    return cfg


@pytest.fixture(scope="module")
def incremental_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root_incremental")
    return write_root(root, {"tiny": tiny_config(),
                             "tiny_incremental": tiny_incremental_config()},
                      [("tiny", "twin_burst_12"),
                       ("tiny_incremental", "twin_burst_12")])


def test_incremental_cell_runs_and_is_correct(incremental_root, cache_dir):
    res = run_tiny(incremental_root, CELL, cache_dir, seed=2**31 + 61,
                   trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for check in res["checks"].values():
        assert check["value"] <= check["limit"]
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["forced_drains"] == 0
    assert metrics["rotation_step_ms"] > 0
    assert metrics["rotation_pause_ms"] > 0


def test_incremental_control_comes_out_incorrect(incremental_root,
                                                 cache_dir):
    """The reference at bf16_3x in the server's place fails a limit."""
    res = run_tiny(incremental_root, CELL, cache_dir, seed=2**31 + 67,
                   control=True)
    sim_err = res["checks"]["sim_err"]
    assert not res["correct"] and sim_err["value"] > sim_err["limit"]


def test_synchronous_cell_reads_its_pause_only(incremental_root,
                                               cache_dir):
    """With ``budget_rows`` 0 the stall is the whole rotation: the pause
    reads it, no slice ran, and no plan was drained."""
    res = run_tiny(incremental_root, "tiny.twin_burst_12", cache_dir,
                   seed=2**31 + 71, trace=True)
    assert res["correct"], res["checks"]
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["rotation_pause_ms"] > 0
    assert "rotation_step_ms" not in metrics
    assert metrics["forced_drains"] == 0

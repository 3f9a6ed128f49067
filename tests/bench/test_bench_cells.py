"""A harness run of each traffic kind through CFServer at a tiny size on
the CPU, checked against the plain reference; and the comparison shown to
fail for the lower-precision control."""
from __future__ import annotations

import pytest

from benchtools import MIXES, run_tiny, tiny_durable_config


@pytest.mark.parametrize("mix", list(MIXES.values()), ids=list(MIXES))
def test_cell_runs_and_is_correct(tiny_root, cache_dir, mix):
    res = run_tiny(tiny_root, f"tiny.{mix}", cache_dir)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    e2e = {"onboard_p90_ms"} if "onboard" in mix \
        or "burst" in mix else {"read_rows_per_s"}
    assert e2e <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    for check in res["checks"].values():
        assert check["value"] <= check["limit"]


@pytest.mark.parametrize("mix", list(MIXES.values()), ids=list(MIXES))
def test_control_comes_out_incorrect(tiny_root, cache_dir, mix):
    """The reference at bf16_3x in the server's place fails a limit."""
    res = run_tiny(tiny_root, f"tiny.{mix}", cache_dir, control=True)
    assert not res["correct"], res["checks"]
    over = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert "sim_err" in over or "read_err" in over


def test_durable_cell_reads_back_through_recovery(tiny_root, cache_dir):
    """Checkpoints every 8 onboards truncate the log inside the window;
    the acknowledged onboards it no longer holds are read back from the
    server a restart recovers, which matches the live one bit for bit."""
    from bench import run
    out = run.run_cell(tiny_root, "tiny_durable.twin_burst_12", 2**31 + 41,
                       1.5, False, require_chip=False, cache_dir=cache_dir)
    res, info = out["result"], out["info"]
    assert res["correct"], res["checks"]
    assert res["checks"]["wal_missing"]["value"] == 0
    assert res["checks"]["recovered_wrong"]["value"] == 0
    assert info["stats_delta"]["snapshots"] >= 1
    # More onboards were truncated than the warm-up sent before the window.
    n_warm = tiny_durable_config()["server"]["snapshot"]["check_every"]
    assert info["readings"]["wal_truncated"] > n_warm


def test_durable_control_comes_out_incorrect(tiny_root, cache_dir):
    """The control fails the deployment checkpointed to disk too: its
    lists are the reference's at bf16_3x; the restart is the program's."""
    res = run_tiny(tiny_root, "tiny_durable.twin_burst_12", cache_dir,
                   control=True)
    sim_err = res["checks"]["sim_err"]
    assert not res["correct"] and sim_err["value"] > sim_err["limit"]
    assert res["checks"]["recovered_wrong"]["value"] == 0


def test_same_seed_same_inputs(tiny_root, cache_dir):
    from bench import datagen, spec, traffic
    cell = spec.load(tiny_root, "tiny.read_zipf")
    cfg = cell.config
    seed = 2**31 + 99

    def make():
        R = datagen.synth_ratings(seed, cfg["n_users"], cfg["n_items"],
                                  cfg["n_ratings"], cfg["min_per_user"])
        return R, traffic.schedule(cell.mix, cfg, R, seed, 2.0)

    (R1, s1), (R2, s2) = make(), make()
    assert (R1 == R2).all()
    assert [(r.due, r.op) for r in s1] == [(r.due, r.op) for r in s2]


def test_control_reads_in_bfloat16():
    """The control's read arithmetic is the reference's in bfloat16: equal
    where every operand and result is exact in bfloat16, within bfloat16
    rounding and far beyond float32's elsewhere."""
    import numpy as np
    from bench import reference
    from bench.control import Lower
    R = np.asarray([[0, 4, 5], [3, 5, 0], [1, 2, 4], [5, 0, 2]], np.int8)
    nbrs = np.asarray([1, 2, 3])
    exact = np.asarray([0.5, 0.25, 0.125])
    assert Lower.prediction(R, nbrs, exact, 1) \
        == reference.prediction(R, nbrs, exact, 1) == 4.0
    sims = np.asarray([0.3127, 0.2911, 0.1733])
    got, want = Lower.scores(R, 0, nbrs, sims), reference.scores(
        R, 0, nbrs, sims)
    assert np.isneginf(got[1:]).all() and np.isneginf(want[1:]).all()
    for g, w in ((got[0], want[0]),
                 (Lower.prediction(R, nbrs, sims, 1),
                  reference.prediction(R, nbrs, sims, 1))):
        assert 1e-5 < abs(g - w) < 2 ** -6 * abs(w)

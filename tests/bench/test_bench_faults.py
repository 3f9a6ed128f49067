"""A run with the timed path broken underneath comes out incorrect: the
harness drives the server as on the chip (its look for a chip skipped),
with one fault planted in the program for each fault a cell can have,
and, for the deployment checkpointed to disk, with a checkpoint lost or
altered before the restart that the run recovers from."""
from __future__ import annotations

import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchtools import run_tiny


def _onboard_unchanged(original):
    def fake(state, *a, **kw):
        _, res = original(state, *a, **kw)
        return state, res
    return fake


def _onboard_altered(original):
    def fake(state, *a, **kw):
        new, res = original(state, *a, **kw)
        row = state.n_active
        vals = new.sim_vals.at[row, -1].add(1e-3)
        return new._replace(sim_vals=vals), res
    return fake


def _add_rating_unchanged(original):
    def fake(state, cache, *a):
        return state, cache
    return fake


def _half_the_neighbours(original):
    def fake(ratings, w, nbrs, users, n_rec=10, **kw):
        k = w.shape[1]
        w = w.at[:, k // 2:].set(0.0)
        return original(ratings, w, nbrs, users, n_rec, **kw)
    return fake


def _half_the_neighbours_predict(original):
    def fake(state, sims, nbrs, item):
        k = sims.shape[0]
        return original(state, sims.at[k // 2:].set(-2.0), nbrs, item)
    return fake


def _score_altered(original):
    def fake(*a, **kw):
        scores, items = original(*a, **kw)
        return scores.at[:, 0].add(1e-3), items
    return fake


def _newest_checkpoint_deleted(original):
    def fake(cfg, R, workdir, *a):
        snaps = (Path(workdir) / "snapshots").iterdir()
        shutil.rmtree(max(snaps, key=lambda p: p.stat().st_mtime_ns))
        return original(cfg, R, workdir, *a)
    return fake


def _checkpoint_row_altered(original):
    def fake(ckpt_dir, step, tree, *a, **kw):
        u = int(tree.n_active) - 1
        star = tree.ratings[u, 0]
        ratings = tree.ratings.at[u, 0].set(
            jnp.where(star >= 5, star - 1, star + 1))
        return original(ckpt_dir, step, tree._replace(ratings=ratings),
                        *a, **kw)
    return fake


FAULTS = {
    # A step that returns its state unchanged.
    "onboard_state_unchanged": ("tiny.twin_burst_12", "repro.core.twinsearch",
                                "onboard_twinsearch", _onboard_unchanged),
    "add_rating_state_unchanged": ("tiny.read_zipf", "repro.core.update",
                                   "add_rating", _add_rating_unchanged),
    # Half of the batch left out, the mean taken over the rest.
    "recommend_half_neighbours": ("tiny.read_uniform",
                                  "repro.serving.cf_server",
                                  "knn_recommend_topn", _half_the_neighbours),
    "predict_half_neighbours": ("tiny.read_zipf", "repro.core.knn",
                                "predict_from_neighbors",
                                _half_the_neighbours_predict),
    # An answer altered where it is produced.
    "onboard_list_altered": ("tiny.fresh_onboard_12", "repro.core.twinsearch",
                             "onboard_twinsearch", _onboard_altered),
    "recommend_score_altered": ("tiny.read_zipf", "repro.serving.cf_server",
                                "knn_recommend_topn", _score_altered),
    # An acknowledged onboard lost, or altered, by the restart.
    "checkpoint_deleted": ("tiny_durable.twin_burst_12", "bench.run",
                           "collect_recovered", _newest_checkpoint_deleted),
    "checkpoint_row_altered": ("tiny_durable.twin_burst_12",
                               "repro.training.checkpoint", "save",
                               _checkpoint_row_altered),
}
# The number that a fault has to put over its limit, where one is named.
CAUGHT_BY = {"checkpoint_deleted": "wal_missing",
             "checkpoint_row_altered": "recovered_wrong"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_comes_out_incorrect(tiny_root, cache_dir, monkeypatch, fault):
    import importlib
    cell, module, name, make = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, make(getattr(mod, name)))
    jax.clear_caches()
    try:
        res = run_tiny(tiny_root, cell, cache_dir)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]
    if fault in CAUGHT_BY:
        check = res["checks"][CAUGHT_BY[fault]]
        assert check["value"] > check["limit"], res["checks"]

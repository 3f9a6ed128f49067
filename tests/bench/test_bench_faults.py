"""A run with the timed path broken underneath comes out incorrect: the
harness drives the server as on the chip (its look for a chip skipped),
with one fault planted in the program for each fault a cell can have."""
from __future__ import annotations

import jax
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


FAULTS = {
    # A step that returns its state unchanged.
    "onboard_state_unchanged": ("twin_burst", "repro.core.twinsearch",
                                "onboard_twinsearch", _onboard_unchanged),
    "add_rating_state_unchanged": ("read_zipf", "repro.core.update",
                                   "add_rating", _add_rating_unchanged),
    # Half of the batch left out, the mean taken over the rest.
    "recommend_half_neighbours": ("read_uniform", "repro.serving.cf_server",
                                  "knn_recommend_topn", _half_the_neighbours),
    "predict_half_neighbours": ("read_zipf", "repro.core.knn",
                                "predict_from_neighbors",
                                _half_the_neighbours_predict),
    # An answer altered where it is produced.
    "onboard_list_altered": ("fresh_onboard", "repro.core.twinsearch",
                             "onboard_twinsearch", _onboard_altered),
    "recommend_score_altered": ("read_zipf", "repro.serving.cf_server",
                                "knn_recommend_topn", _score_altered),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_comes_out_incorrect(tiny_root, cache_dir, monkeypatch, fault):
    import importlib
    mix, module, name, make = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, make(getattr(mod, name)))
    jax.clear_caches()
    try:
        res = run_tiny(tiny_root, f"tiny.{mix}", cache_dir)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]

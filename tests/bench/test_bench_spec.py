"""The benchmark's contract and its discovery by name: BENCHMARK.json's
shape, the command's refusal without a TPU, data and schedules made from
the seed, and a deployment, mix and metric added as files only."""
from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchtools import REPO, run_tiny, tiny_config, write_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_json_names_its_files():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in s["configs"]}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    cells = {w["name"]: w for w in s["workloads"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.25 for m in e2e.values())
    for c in configs.values():
        assert NAME.match(c["name"]) and (REPO / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in cells.values())
        data = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(data["reduced"])
        for key in c["reduced"]:
            assert data["published"][key] != data[key]
    for w in cells.values():
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] == 1
        assert (REPO / f"bench/traffic/{w['traffic']}.json").is_file()
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"])
        assert (REPO / f"bench/metrics/{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in s["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "ml1m.read_zipf",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def test_unknown_device_has_no_peaks():
    from bench import spec as spec_lib
    peaks = json.loads((REPO / "bench/peaks.json").read_text())
    assert spec_lib.device_peaks(peaks, "TPU v5 lite")["hbm_bytes_per_s"] \
        == 819e9
    with pytest.raises(KeyError):
        spec_lib.device_peaks(peaks, "cpu")


def test_ratings_from_the_seed():
    from bench import datagen
    R = datagen.synth_ratings(2**31 + 11, 300, 500, 6000, min_per_user=10)
    assert np.count_nonzero(R) == 6000
    assert (np.count_nonzero(R, axis=1) >= 10).all()
    assert set(np.unique(R)) <= {0, 1, 2, 3, 4, 5}
    fresh = datagen.fresh_profiles(np.random.default_rng(1), 40,
                                   datagen.item_biases(2**31 + 11, 500), 20,
                                   R)
    assert (np.count_nonzero(fresh, axis=1) == 20).all()
    keys = {r.tobytes() for r in fresh}
    assert len(keys) == 40 and not keys & {r.tobytes() for r in R}


def test_every_seed_gets_the_same_work():
    from bench import datagen, traffic
    cfg = tiny_config()
    mix = traffic.load(REPO / "bench/traffic/read_zipf.json")
    R = datagen.synth_ratings(1, cfg["n_users"], cfg["n_items"],
                              cfg["n_ratings"], cfg["min_per_user"])
    a = traffic.schedule(mix, cfg, R, 2**31 + 1, 3.0)
    b = traffic.schedule(mix, cfg, R, 2**31 + 2, 3.0)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_per_s"] * 3)
    gaps = [np.sort(np.diff([r.due for r in s])) for s in (a, b)]
    assert max(r.due for r in a) < 3.0 and a[0].due == 0.0
    assert sorted(r.op for r in a) == sorted(r.op for r in b)
    assert [r.op for r in a] != [r.op for r in b]
    assert [r.due for r in a] == [r.due for r in b]
    assert np.std(gaps[0]) > 0.5 * np.mean(gaps[0])   # exponential, not even
    B = traffic.BLOCK
    for k in range(0, len(a), B):
        assert sorted(r.op for r in a[k:k + B]) == \
            sorted(r.op for r in b[k:k + B])


def test_nothing_compiles_in_the_window(tiny_root, tmp_path, monkeypatch):
    """The window rotates the arena; the first run fills the persistent
    cache in set-up, so neither it nor a later run compiles in its
    window.  The server's straggler monitor is held off: on a loaded test
    machine three slow calls in a row walk its ladder to the traditional
    path, whose program only that timing reaches."""
    import repro.serving.cf_server as cf_server
    from bench import run
    monitor = cf_server.StragglerMonitor
    monkeypatch.setattr(cf_server, "StragglerMonitor", lambda **kw: monitor(
        **{**kw, "straggler_ratio": math.inf}))
    cache = tmp_path / "cache"
    for _ in range(2):
        out = run.run_cell(tiny_root, "tiny.twin_burst_12", 2**31 + 21, 3.0,
                           False, require_chip=False, cache_dir=cache)
        assert out["result"]["correct"], out["result"]["checks"]
        c = out["info"]["compile_in_window"]
        assert out["info"]["ops"]["onboard"] > 8 and c["programs"] > 0
        assert c["programs"] == c["cache_hits"], c
        assert len(list((cache / "bench_marks").iterdir())) == 1


def test_added_files_are_found_by_name(tmp_path):
    """A new deployment, traffic mix and per-layer metric, each a file of
    its own plus entries in BENCHMARK.json, with no other edit."""
    cfg = tiny_config()
    cfg.update(name="tiny2", n_users=40, n_ratings=400)
    root = write_root(tmp_path, {"tiny2": cfg}, [("tiny2", "mixed_tiny")])
    (root / "bench/traffic").mkdir(parents=True)
    (root / "bench/traffic/mixed_tiny.json").write_text(json.dumps({
        "arrivals": {"process": "poisson", "rate_per_s": 12.0},
        "ops": [{"op": "onboard", "share": 0.5, "profile": "fresh",
                 "ratings_per_profile": "mean"},
                {"op": "onboard", "share": 0.5,
                 "profile": "copy_of_base"}]}))
    (root / "bench/metrics").mkdir(parents=True)
    (root / "bench/metrics/onboards.window.py").write_text(
        "def read(run):\n    return run.delta('onboarded')\n")
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["per_layer"].append({
        "name": "onboards.window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "TwinSearch onboard",
        "moves": "onboard_p90_ms", "workloads": ["tiny2.mixed_tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    res = run_tiny(root, "tiny2.mixed_tiny", tmp_path / "cache", trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["onboards.window"]["value"] == res["attempted"]
    assert 0 < res["metrics"]["twin_hit_share"]["value"] < 1

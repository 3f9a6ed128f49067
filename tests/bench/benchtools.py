"""Tiny deployments for the benchmark's CPU tests.

``tiny_root`` writes a benchmark root with one small deployment and a
cell for each of the repository's traffic mixes, and the same deployment
checkpointed to disk under ``twin_burst_12``; the mixes, the metric readers
and the peak table are found beside the benchmark (``bench/``).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# The repository's traffic mixes (``bench/traffic/``), by the kind of
# traffic each sends: the tests' ids.
MIXES = {"twin_burst": "twin_burst_12", "fresh_onboard": "fresh_onboard_12",
         "read_zipf": "read_zipf", "read_uniform": "read_uniform"}


def tiny_config() -> dict:
    cfg = json.loads((REPO / "bench/configs/ml1m.json").read_text())
    cfg.update(name="tiny", n_users=48, n_items=40, n_ratings=480,
               min_per_user=5)
    cfg["server"]["capacity_extra"] = 8
    # Set from CPU readings at this size: the program's sim_err, unit_err
    # and read_err stay under 1.1e-7, 2.4e-7 and 1.3e-6 on three seeds, the
    # control's over 4.8e-6, 1.1e-5 and 0.06.
    cfg["limits"] = {"sim_err": 1e-6, "unit_err": 2e-6, "rows_wrong": 0,
                     "twin_wrong": 0, "wal_missing": 0, "read_err": 1e-4,
                     "rec_wrong": 0, "recovered_wrong": 0}
    return cfg


def tiny_durable_config() -> dict:
    """The tiny deployment checkpointed to disk every 8 onboards, so that
    a window truncates the log: its onboards are read back through what a
    restarted server recovers."""
    cfg = tiny_config()
    cfg["name"] = "tiny_durable"
    cfg["server"]["snapshot"].update(every=8, disk=True)
    return cfg


def write_root(root: Path, configs: dict, cells: list[tuple[str, str]]
               ) -> Path:
    """A benchmark root: ``configs`` by name, one cell per (config, mix),
    the repository's metrics applied to every cell."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench/configs").mkdir(parents=True, exist_ok=True)
    spec["configs"] = []
    for name, cfg in configs.items():
        rel = f"bench/configs/{name}.json"
        (root / rel).write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test", "file": rel,
                                "reduced": [], "why": "test"})
    spec["workloads"] = [{"name": f"{c}.{m}", "config": c, "traffic": m,
                          "chips": 1, "why": "test"} for c, m in cells]
    for metric in spec["end_to_end"] + spec["per_layer"]:
        metric.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_tiny(root: Path, cell: str, cache: Path, seed: int = 2**31 + 5,
             trace: bool = False, **kw) -> dict:
    from bench import run
    return run.run_cell(root, cell, seed, 1.5, trace, require_chip=False,
                        cache_dir=cache, **kw)["result"]

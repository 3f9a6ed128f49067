"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one deployment, one traffic mix or one
per-layer metric is a file of its own, found by name:

  * the deployment: the ``file`` of its entry under ``configs``;
  * the traffic mix: ``bench/traffic/<traffic>.json``;
  * a per-layer metric: the reader ``bench/metrics/<metric>.py``, a module
    with ``read(run) -> float | None``.

``root`` is the directory that holds ``BENCHMARK.json``; a file that is
not under its ``bench/`` is looked up beside this module, so a root made
for a test may bring only the files it adds.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    peaks: dict


def find(root: Path, rel: str) -> Path:
    for base in (Path(root), HERE.parent):
        if (base / rel).is_file():
            return base / rel
    raise FileNotFoundError(f"no {rel} under {root} or {HERE.parent}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(root: Path, workload: str, rate: float | None = None) -> Cell:
    """The cell ``workload``; ``rate`` replaces its mix's arrival rate."""
    from bench import traffic
    spec = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads(find(root, conf["file"]).read_text())
    mix = traffic.load(find(root, f"bench/traffic/{w['traffic']}.json"))
    if rate is not None:
        mix["arrivals"]["rate_per_s"] = float(rate)
    peaks = json.loads(find(root, "bench/peaks.json").read_text())
    return Cell(
        name=workload, workload=w, config=config, mix=mix,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        peaks=peaks)


def reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = find(root, f"bench/metrics/{metric}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def device_peaks(peaks: dict, kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device missing from the
    table is an error."""
    try:
        return peaks["devices"][kind]
    except KeyError:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(peaks['devices'])})") from None

"""Reduction of a profiler trace (``*.xplane.pb``) to what the metrics
read.

The device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation the chip ran, and the device is busy wherever
one runs.  The host plane carries the benchmark's own spans
(``jax.profiler.TraceAnnotation``): ``window`` around the measured
window, one span per request named after the server call
(``onboard_user``, ``recommend_batch``, ``predict_batch``,
``add_rating``), and ``wait`` while the generator sleeps.  Both are on
one clock, so device time can be attributed to the request that was
being served and an idle gap to what the host was doing.

    python3 -m bench.trace <trace dir>      # what the trace holds
"""
from __future__ import annotations

import glob
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_NAMES = ("window", "wait", "onboard_user", "recommend_batch",
              "predict_batch", "add_rating")


def _xplane(trace_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return Path(found[-1])


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, sorted."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, np.float64)


@dataclass
class Trace:
    """Device operations and host spans in one trace, in nanoseconds.

    ``ops``: (name, start, end) of every device operation, over all the
    devices the run used; ``spans``: (name, start, end) of the
    benchmark's host spans; ``n_devices``: how many device planes."""
    ops: list
    spans: list
    n_devices: int = 1
    window: tuple = (0.0, 0.0)
    busy: np.ndarray = field(default=None, repr=False)

    def __post_init__(self):
        w = [s for s in self.spans if s[0] == "window"]
        if w:
            self.window = (w[0][1], w[0][2])
        elif self.ops:
            self.window = (min(o[1] for o in self.ops),
                           max(o[2] for o in self.ops))
        a, b = self.window
        iv = np.asarray([(max(s, a), min(e, b)) for _, s, e in self.ops
                         if e > a and s < b], np.float64)
        self.busy = _merge(iv.reshape(-1, 2))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices
        (one device: the union of its operations' intervals)."""
        return float((self.busy[:, 1] - self.busy[:, 0]).sum()) * 1e-9 \
            / max(1, self.n_devices)

    def busy_between(self, a: float, b: float) -> float:
        """Busy seconds inside [a, b)."""
        lo = np.clip(self.busy[:, 0], a, b)
        hi = np.clip(self.busy[:, 1], a, b)
        return float((hi - lo).sum()) * 1e-9 / max(1, self.n_devices)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]

    def op_seconds(self, match) -> float:
        """Total device seconds of the operations whose name satisfies
        ``match``."""
        return sum(e - s for n, s, e in self.ops if match(n)) * 1e-9

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Every stretch of the window with nothing on the device, longest
        first, named after the host span around its middle."""
        a, b = self.window
        edges = np.concatenate([[a], self.busy.ravel(), [b]]).reshape(-1, 2)
        gaps = [(s, e) for s, e in edges if e > s]
        inner = [s for s in self.spans if s[0] != "window"]
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            host = next((n for n, hs, he in inner if hs <= mid < he),
                        "between spans")
            out.append((host, (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self, top: int = 10) -> dict:
        total: dict[str, float] = {}
        for n, s, e in self.ops:
            total[n] = total.get(n, 0.0) + (e - s) * 1e-9
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[n, t] for n, t in self.idle_gaps()[:top]]}


def load(path: Path) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, spans, n_dev = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            n_dev += bool(lines)
            for ln in lines:
                ops += [(e.name, e.start_ns, e.end_ns) for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in ln.events
                          if e.name in SPAN_NAMES]
    spans.sort(key=lambda s: s[1])
    return Trace(ops=ops, spans=spans, n_devices=max(1, n_dev))


def reduce_dir(trace_dir: Path) -> Trace:
    return load(_xplane(trace_dir))


def describe(trace_dir: Path, top: int = 40) -> dict:
    """Planes, lines and the event names that took most time, each with
    its count, seconds and the statistics of its first event: what to
    look at before writing a reader against a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(_xplane(trace_dir)))
    out = {}
    for plane in pd.planes:
        lines = {}
        for ln in plane.lines:
            count: dict[str, list] = {}
            for e in ln.events:
                c = count.setdefault(e.name, [0, 0.0, None])
                c[0] += 1
                c[1] += e.duration_ns * 1e-9
                if c[2] is None:
                    c[2] = {k: str(v)[:300] for k, v in e.stats}
            lines[ln.name] = sorted(count.items(),
                                    key=lambda kv: -kv[1][1])[:top]
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    print(json.dumps(describe(Path(sys.argv[1])), indent=1))

"""The server's own spans and each program's device time in a profiler
trace, beside the reduction of ``bench/trace.py``.

``CFServer`` opens a span at each stage of its request path
(``repro.serving.tracing.SPANS``, every name starting ``cf.``), and the
device plane's ``XLA Modules`` line holds one event per run of a jitted
program, named after it.  ``load`` reads a trace as ``bench/trace.py``
does and keeps both as well, on the same clock, in a ``SpanTrace``: a
``Trace`` whose idle gaps are named ``<request>/<stage>`` where a stage
span covers the gap's middle.  ``READINGS`` are what the program spans
give per request: each takes a trace and returns None where it finds
nothing to read, as in a trace of a server without the spans.

    python3 -m bench.spans <trace dir>    # host time per request by stage,
                                          # the readings, the named gaps
"""
from __future__ import annotations

import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import trace as trace_lib

PREFIX = "cf."
MODULES_LINE = "XLA Modules"
READS = ("recommend_batch", "predict_batch")
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class SpanTrace(trace_lib.Trace):
    """``program_spans``: (name, start, end) of every ``cf.*`` span, by
    start, a span before those it encloses; ``modules``: (program name
    without its ``(id)``, start, end) of every program run on a device."""
    program_spans: list = field(default_factory=list)
    modules: list = field(default_factory=list)

    def __post_init__(self):
        super().__post_init__()
        self.program_spans = sorted(self.program_spans,
                                    key=lambda s: (s[1], -s[2]))
        self._starts = np.asarray([s for _, s, _ in self.program_spans],
                                  np.float64)
        self._ends = np.asarray([e for _, _, e in self.program_spans],
                                np.float64)

    def stage_at(self, t: float) -> str | None:
        """The innermost program span open at ``t``."""
        hit = np.flatnonzero((self._starts <= t) & (self._ends > t))
        return self.program_spans[hit[-1]][0] if hit.size else None

    def idle_gaps(self) -> list[tuple[str, float]]:
        """As ``Trace.idle_gaps``, with the stage the host was in added
        after a ``/`` where a program span covers the gap's middle."""
        a, b = self.window
        edges = np.concatenate([[a], self.busy.ravel(), [b]]).reshape(-1, 2)
        inner = [s for s in self.spans if s[0] != "window"]
        out = []
        for s, e in edges:
            if e <= s:
                continue
            mid = (s + e) / 2
            host = next((n for n, hs, he in inner if hs <= mid < he),
                        "between spans")
            stage = self.stage_at(mid)
            out.append((f"{host}/{stage}" if stage else host, (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def in_window(self, name: str) -> list:
        """The window's program spans called ``name``."""
        a, b = self.window
        return [s for s in self.program_spans
                if s[0] == name and s[1] >= a and s[2] <= b]

    def requests(self, names) -> list:
        return [s for n in names for s in self.named(n)]

    def module_seconds(self, name: str) -> float:
        """Device seconds of the program ``name`` inside the window."""
        a, b = self.window
        return sum(max(0.0, min(e, b) - max(s, a))
                   for n, s, e in self.modules if n == name) * 1e-9

    def stages_within(self, start: float, end: float) -> dict:
        """Seconds of each program span inside [start, end), and under
        ``"(any stage)"`` the union of them all."""
        lo, hi = np.searchsorted(self._starts, [start, end], side="left")
        inside = [s for s in self.program_spans[lo:hi] if s[2] <= end]
        out: dict[str, float] = {}
        for n, s, e in inside:
            out[n] = out.get(n, 0.0) + (e - s) * 1e-9
        iv = np.asarray([(s, e) for _, s, e in inside], np.float64)
        union = trace_lib._merge(iv.reshape(-1, 2))
        out["(any stage)"] = float((union[:, 1] - union[:, 0]).sum()) * 1e-9
        return out


def load(path: Path) -> SpanTrace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    base = trace_lib.load(path)
    program, modules = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:TPU:"):
            for ln in plane.lines:
                if ln.name == MODULES_LINE:
                    modules += [(_MODULE_ID.sub("", e.name), e.start_ns,
                                 e.end_ns) for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                program += [(e.name, e.start_ns, e.end_ns) for e in ln.events
                            if e.name.startswith(PREFIX)]
    return SpanTrace(ops=base.ops, spans=base.spans,
                     n_devices=base.n_devices, program_spans=program,
                     modules=modules)


def reduce_dir(trace_dir: Path) -> SpanTrace:
    return load(trace_lib._xplane(trace_dir))


# ---------------------------------------------------------------------------
# Readings
# ---------------------------------------------------------------------------

def _has_spans(t) -> bool:
    return bool(getattr(t, "program_spans", None))


def _stage_ms_per_request(t, stage: str, requests) -> float | None:
    """Milliseconds of the window's ``stage`` spans over the number of
    its requests called one of ``requests``."""
    if not _has_spans(t):
        return None
    n = len(t.requests(requests))
    if not n:
        return None
    return sum(e - s for _, s, e in t.in_window(stage)) * 1e-6 / n


def wal_append_ms(t) -> float | None:
    """Mean length of the window's ``cf.wal.append`` spans: encode,
    write, flush and fsync of one write-ahead record."""
    spans = t.in_window("cf.wal.append") if _has_spans(t) else []
    return sum(e - s for _, s, e in spans) * 1e-6 / len(spans) \
        if spans else None


def health_check_ms(t) -> float | None:
    """Milliseconds of arena health sweep (``cf.health_check``: the sweep
    and its sync) per onboard of the window."""
    return _stage_ms_per_request(t, "cf.health_check", ("onboard_user",))


def read_validate_ms(t) -> float | None:
    """Milliseconds of per-row id validation per read request."""
    return _stage_ms_per_request(t, "cf.read.validate", READS)


def read_dedup_ms(t) -> float | None:
    """Milliseconds of twin-dedup keys, ``dedup_rows`` and bucket padding
    per read request."""
    return _stage_ms_per_request(t, "cf.read.dedup", READS)


def read_fanout_ms(t) -> float | None:
    """Milliseconds of answers built on the host per read request."""
    return _stage_ms_per_request(t, "cf.read.fanout", READS)


def add_rating_device_ms(t) -> float | None:
    """Device milliseconds of the window's ``jit_add_rating`` programs
    over its ``add_rating`` requests."""
    if not getattr(t, "modules", None):
        return None
    n = len(t.named("add_rating"))
    return t.module_seconds("jit_add_rating") * 1e3 / n if n else None


READINGS = {f.__name__: f for f in (
    wal_append_ms, health_check_ms, read_validate_ms, read_dedup_ms,
    read_fanout_ms, add_rating_device_ms)}


# ---------------------------------------------------------------------------
# What a trace says
# ---------------------------------------------------------------------------

def request_split(t: SpanTrace) -> dict:
    """For each kind of request: how many, their mean milliseconds, the
    mean milliseconds of each stage inside one, the mean milliseconds of
    the request no stage covers, and the smallest share of a request that
    stages cover."""
    out = {}
    for name in trace_lib.SPAN_NAMES:
        if name in ("window", "wait") or not t.named(name):
            continue
        reqs = t.named(name)
        totals: dict[str, float] = {}
        covered = []
        for _, s, e in reqs:
            within = t.stages_within(s, e)
            for k, v in within.items():
                totals[k] = totals.get(k, 0.0) + v
            covered.append(within["(any stage)"] / max(1e-9, (e - s) * 1e-9))
        mean_ms = sum(e - s for _, s, e in reqs) * 1e-6 / len(reqs)
        stages = {k: v * 1e3 / len(reqs) for k, v in sorted(totals.items())}
        out[name] = {"n": len(reqs), "mean_ms": mean_ms,
                     "stage_ms": stages,
                     "no_stage_ms": mean_ms - stages.get("(any stage)", 0.0),
                     "min_cover": min(covered)}
    return out


def summary(t: SpanTrace, top: int = 20) -> dict:
    programs: dict[str, list] = {}
    for n, s, e in t.modules:
        p = programs.setdefault(n, [0, 0.0])
        p[0] += 1
        p[1] += (e - s) * 1e-9
    return {"window_s": t.window_s, "busy_s": t.busy_s,
            "readings": {k: f(t) for k, f in READINGS.items()},
            "requests": request_split(t),
            "programs": dict(sorted(programs.items(),
                                    key=lambda kv: -kv[1][1])),
            "idle_gaps": t.idle_gaps()[:top]}


if __name__ == "__main__":
    print(json.dumps(summary(reduce_dir(Path(sys.argv[1]))), indent=1))

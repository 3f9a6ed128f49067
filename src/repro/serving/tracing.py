"""Named spans inside ``CFServer``'s request path, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: the mechanism and the clock
of the device trace, so a profile of a running server puts device time
and idle gaps beside the stage the host was in.  With no profiler running
a span records nothing and costs under a microsecond.  A span only wraps
work that already runs: it adds no sync and no host transfer.

Each span is opened once per call of the code it names, never inside a
per-row loop.  ``SPANS`` is the contract that trace readers rely on: the
benchmark's reduction (``bench/spans.py``) and an operator reading a
profile of a live server.
"""
from __future__ import annotations

import jax

SPANS = (
    "cf.onboard.rotate",    # an onboard's rotation: merge, block, re-wrap
    "cf.onboard.run",       # the onboard program: dispatch, any re-trace or
                            # cache load at a new arena shape, device, block
    "cf.health_check",      # the arena invariant sweep and its host sync
    "cf.wal.append",        # one WAL record: encode, write, flush, fsync
    "cf.read.validate",     # a read batch's per-row id checks
    "cf.read.probe",        # the probe program and its transfer to the host
    "cf.read.dedup",        # twin-dedup keys, dedup_rows, bucket padding
    "cf.read.score",        # the score program and its transfer to the host
    "cf.read.fanout",       # the answers built on the host
    "cf.add_rating.apply",  # cache init, scalar conversions, the dispatch
    "cf.rotation.step",     # one slice of an incremental rotation: the
                            # merge's dispatch, its device time, the rows
                            # written into the plan's accumulators
    "cf.rotation.swap",     # an incremental rotation's swap: the
                            # rotate_commit record, the last of the plan,
                            # the new arena installed, the programs re-wrapped
)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The span ``name`` (one of ``SPANS``), as a context manager."""
    return jax.profiler.TraceAnnotation(name)

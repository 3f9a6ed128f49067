"""50th percentile over every onboard of the window, from its due time
to the return of the call that acknowledged it (after its WAL fsync); a
failed onboard counts as infinitely slow.  At the cells' rate most
onboards queue behind a rotation stall, so the median is the typical wait
in that queue: too unsteady from run to run to bound end to end (see
PERF.md), kept as the rotation layer's reading."""
from bench.run import percentile


def read(run):
    return percentile(run.latencies_ms(("onboard",)), 50)

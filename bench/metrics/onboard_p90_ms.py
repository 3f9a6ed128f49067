"""90th percentile over every onboard of the window, from its due time
to the return of the call that acknowledged it (after its WAL fsync); a
failed onboard counts as infinitely slow."""
from bench.run import percentile


def read(run):
    return percentile(run.latencies_ms(("onboard",)), 90)

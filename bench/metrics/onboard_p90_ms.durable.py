"""``onboard_p90_ms`` of a deployment that checkpoints to disk, under a
bound of its own: there the p90 lies deep in the queue behind each
checkpoint's stall and spreads far less from run to run than in the
deployments without, so a smaller loss on the durability path shows."""
from bench.run import percentile


def read(run):
    return percentile(run.latencies_ms(("onboard",)), 90)

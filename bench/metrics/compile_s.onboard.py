"""Seconds JAX spent inside the window tracing, lowering and compiling or
loading programs from the persistent cache: the server re-wraps its jitted
programs after every rotation, and the first onboard at the new arena
shape pays for it."""


def read(run):
    return sum(run.compile["seconds"].values())

"""Incremental rotations the window had to finish synchronously: the
server's ``forced_drains`` over the window.  Above 0, a plan did not
finish within the reserve of write slots and the onboard that found the
arena full paid for the rest of it, as a synchronous rotation does."""


def read(run):
    if "forced_drains" not in run.stats1 or "forced_drains" \
            not in run.stats0:
        return None
    return run.delta("forced_drains")

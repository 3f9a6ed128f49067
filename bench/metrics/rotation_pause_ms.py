"""Milliseconds one request stalls for a rotation: the server's
``rotation_pause_s`` over its ``rotations``, both over the window.  The
stall is the whole rotation where it runs synchronously, the swap (the
``rotate_commit`` record, the last of the plan, the new arena installed
and the programs re-wrapped) where it runs in slices.  A server without
the counter, or a window without a rotation, gives nothing."""


def read(run):
    if "rotation_pause_s" not in run.stats1 or "rotation_pause_s" \
            not in run.stats0:
        return None
    n = run.delta("rotations")
    return run.delta("rotation_pause_s") * 1e3 / n if n else None

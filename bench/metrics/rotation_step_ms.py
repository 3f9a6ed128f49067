"""Milliseconds of one slice of an incremental rotation on the request
path: the server's ``plan_step_s`` over its ``plan_steps``, both over the
window (the merge's dispatch, its device time and the copy into the
plan's accumulators).  A server without the counters, or a window with no
slice, gives nothing."""


def read(run):
    if "plan_steps" not in run.stats1 or "plan_steps" not in run.stats0:
        return None
    n = run.delta("plan_steps")
    return run.delta("plan_step_s") * 1e3 / n if n else None

"""Device busy milliseconds inside an onboard's ``onboard_user`` span,
mean over the window's onboards that did not rotate the arena (a
rotation's merge runs inside the onboard that triggers it; ``rotation_ms``
reads that).  Traced runs only."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    spans = run.trace.named("onboard_user")
    issued = [o for o in run.outcomes
              if o.req.op == "onboard" and o.info.get("status") != "late"]
    if not spans or len(spans) != len(issued):
        return None
    ms = [run.trace.busy_between(s, e) * 1e3
          for (_, s, e), o in zip(spans, issued) if not o.info.get("rotated")]
    return sum(ms) / len(ms) if ms else None

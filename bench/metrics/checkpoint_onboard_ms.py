"""Mean service time, from the call's start to its return, of the
window's onboards that wrote a disk checkpoint (``ServerStats.snapshots``
read before and after each call), over those that did not also rotate the
arena; where every one rotated, over all of them.  Deployments with disk
checkpoints only."""


def read(run):
    if not run.config["server"]["snapshot"]["disk"]:
        return None
    took = [o for o in run.outcomes if o.req.op == "onboard" and o.ok
            and o.info.get("snapshots")]
    ms = [(o.end - o.start) * 1e3
          for o in ([o for o in took if not o.info["rotated"]] or took)]
    return sum(ms) / len(ms) if ms else None

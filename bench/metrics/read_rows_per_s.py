"""Read rows answered per second: the rows of every recommend_batch and
predict_batch call the window acknowledged, over the whole window, from
its opening to the return of its last request.  The read cells offer
more than the server can answer, so this is its read capacity under the
mix's background writes."""


def read(run):
    rows = sum(len(o.req.args["users"]) for o in run.outcomes
               if o.req.op in ("recommend", "predict") and o.ok)
    return rows / run.window_s if run.window_s > 0 else None

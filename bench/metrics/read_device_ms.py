"""Device busy milliseconds inside a read request's span
(``recommend_batch`` or ``predict_batch``), mean over the window's reads.
Traced runs only."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    spans = run.trace.named("recommend_batch") + run.trace.named(
        "predict_batch")
    if not spans:
        return None
    return sum(run.trace.busy_between(s, e) for _, s, e in spans) * 1e3 \
        / len(spans)

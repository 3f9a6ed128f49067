"""Share of the window's onboards that found a twin and copied its list
(``ServerStats.twin_hits / onboarded`` over the window)."""


def read(run):
    n = run.delta("onboarded")
    return run.delta("twin_hits") / n if n else None

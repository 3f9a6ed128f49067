"""Share of the window's read rows that twin-query dedup answered without
scoring them: ``1 - query_unique / queries`` over the window."""


def read(run):
    n = run.delta("queries")
    return 1.0 - run.delta("query_unique") / n if n else None

"""Mean of the rotations the window triggered, as the server times them
(``ServerStats.rotation_ms``: the merge, before the programs re-wrap)."""


def read(run):
    ms = run.rotation_ms
    return sum(ms) / len(ms) if ms else None

"""Share of its roofline that the ``knn_score`` kernel reaches: the least
time its calls could take on the chip over their device time in the
trace, in per cent.

The kernel appears in the trace as the custom call ``%knn_scores`` with
the output ``f32[B, M]`` (M: the items padded to the tile) and first
operand ``s32[B, k]`` (each row's neighbours).  What a call of B rows
needs to move is ``4 m B (k + 2)`` bytes for m items: the B k
neighbours' rating rows, the B users' own rows (their seen items) and
the B score rows it writes; its operations (2 m B k multiply-adds, twice)
are far below the chip's peak for that many bytes, so the bytes bound
it.  Padding to M, the 8-row tile over-read and rows repeated to fill
the batch bucket are not counted, so they show as a lower share."""
import re

CALL = re.compile(r"^%knn_scores[.\d]* = f32\[(\d+),(\d+)\][^ ]* "
                  r"custom-call\(s32\[(\d+),(\d+)\]")


def bytes_needed(m: int, rows: int, k: int) -> int:
    return 4 * m * rows * (k + 2)


def read(run):
    if run.trace is None or not run.peaks:
        return None
    m = run.config["n_items"]
    least = seconds = 0.0
    for name, start, end in run.trace.ops:
        call = CALL.match(name)
        if call:
            rows, k = int(call[3]), int(call[4])
            least += bytes_needed(m, rows, k) / run.peaks["hbm_bytes_per_s"]
            seconds += (end - start) * 1e-9
    return 100.0 * least / seconds if seconds else None

"""Mean MB per cell that capture and restore copied on the host (the
program counter ``copied_bytes``, an exact count): each chunk encoded from
a payload, each restored array filled from its chunks, and each fallback
copy of a whole payload.  None on a program without the counter."""
from program_spans import counted, window


def read(run):
    w = window()
    if w is None or "copied_bytes" not in w["counters"]:
        return None
    return counted(run, "copied_bytes", 1e-6)

"""Mean MB per cell that a migration's delta digested and kept back,
because the destination already knew them unchanged (the program counter
``kept_bytes``, an exact count).  None on a program without the
counter."""
from program_spans import counted, window


def read(run):
    w = window()
    if w is None or "kept_bytes" not in w["counters"]:
        return None
    return counted(run, "kept_bytes", 1e-6)

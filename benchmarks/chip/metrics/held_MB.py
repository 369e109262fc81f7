"""Mean MB per cell of sent names' chunks that the destination's chunk
store already held, so that they did not cross (the program counter
``held_bytes``, an exact count).  None on a program without the
counter."""
from program_spans import counted, window


def read(run):
    w = window()
    if w is None or "held_bytes" not in w["counters"]:
        return None
    return counted(run, "held_bytes", 1e-6)

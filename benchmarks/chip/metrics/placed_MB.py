"""Mean MB per cell that restore placed on a device: arrays captured from
a device come back there (the program counter ``placed_bytes``, an exact
count).  None on a program without the counter."""
from program_spans import counted, window


def read(run):
    w = window()
    if w is None or "placed_bytes" not in w["counters"]:
        return None
    return counted(run, "placed_bytes", 1e-6)

"""Mean MB per cell of uint32 grids the hash programs read (the program
counter ``widened_bytes``: rows x 1024 x 4 per launch, an exact count)."""
from program_spans import counted


def read(run):
    return counted(run, "widened_bytes", 1e-6)

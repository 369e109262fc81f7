"""Mean MB per cell pulled from device arrays to the host (the program
counter ``pull_bytes``, an exact count)."""
from program_spans import counted


def read(run):
    return counted(run, "pull_bytes", 1e-6)

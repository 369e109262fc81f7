"""Mean MB per cell uploaded to the device to be hashed (the program
counter ``upload_bytes``, an exact count)."""
from program_spans import counted


def read(run):
    return counted(run, "upload_bytes", 1e-6)

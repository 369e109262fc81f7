"""Mean device->host syncs per cell of the hash path (the program counter
``host_syncs``)."""
from program_spans import counted


def read(run):
    return counted(run, "host_syncs")

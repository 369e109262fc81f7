"""Mean seconds per cell in the program span ``reducer.payload``: the flat
``uint8`` view of each captured host array, a copy only for an array that
is not C-contiguous or holds objects (core/reducer.py)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "reducer.payload")

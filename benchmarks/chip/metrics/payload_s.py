"""Mean seconds per cell in the program span ``reducer.payload``: the
contiguous ``tobytes`` copy of each captured array (core/reducer.py)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "reducer.payload")

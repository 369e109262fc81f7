"""Mean seconds per cell in the program span ``reducer.pickle``: pickling
each captured name and compressing its stream (core/reducer.py)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "reducer.pickle")

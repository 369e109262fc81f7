"""Mean seconds per cell in the program span ``reducer.encode``: splitting
payloads into chunks and encoding them (core/reducer.py)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "reducer.encode")

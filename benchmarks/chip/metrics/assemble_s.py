"""Mean seconds per cell in the program span ``reducer.assemble``:
fetching, decoding and joining a restored array's chunks
(core/reducer.py)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "reducer.assemble")

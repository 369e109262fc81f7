"""Mean seconds per cell in the program span ``hash.wait``: launching a
hash program and waiting for its lanes to reach the host."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "hash.wait")

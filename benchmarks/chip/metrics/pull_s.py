"""Mean seconds per cell in the program span ``reducer.pull``: device
arrays pulled to the host (``reducer.host_array``)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "reducer.pull")

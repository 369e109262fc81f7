"""Mean seconds per cell in the program span ``hash.stage``: host copies
into the hash path's staging buffers (kernels/hash_delta/ops.py,
core/chunkstore.py)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "hash.stage")

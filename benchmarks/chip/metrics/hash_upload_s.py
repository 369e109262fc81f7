"""Mean seconds per cell in the program span ``hash.upload``: staged host
bytes placed on the device to be hashed (``ops.to_device``)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "hash.upload")

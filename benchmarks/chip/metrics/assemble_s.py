"""Mean seconds per cell in the program span ``reducer.assemble``:
fetching each chunk of a restored array (a view of a codec-``none`` body,
decompressed bytes otherwise) and filling the destination array in place;
quantized arrays are joined and dequantized instead (core/reducer.py)."""
from program_spans import span_seconds


def read(run):
    return span_seconds(run, "reducer.assemble")

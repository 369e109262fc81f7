"""Programs JAX lowered inside the traced window, in all (not per cell):
the recorder counts one per lowering event.  Zero is a reading."""
from program_spans import window


def read(run):
    w = window()
    return None if w is None else sum(w["lowerings"].values())

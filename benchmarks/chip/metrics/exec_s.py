"""Mean seconds per cell spent in the benchmark's ``exec`` span (see
probes.py for the program call it wraps)."""


def read(run):
    return run.spans.get("exec", 0.0) / run.cells if run.cells else None

"""Mean seconds per cell spent in the benchmark's ``migrate`` span (see
probes.py for the program call it wraps)."""


def read(run):
    return run.spans.get("migrate", 0.0) / run.cells if run.cells else None

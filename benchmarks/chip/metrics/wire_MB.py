"""Mean MB per cell that crossed to a destination
(``MigrationResult.nbytes``, an exact count)."""


def read(run):
    return run.wire_bytes / 1e6 / run.cells if run.cells else None

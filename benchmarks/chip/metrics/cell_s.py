"""Mean turnaround per cell: the window's wall seconds over the cells
completed in it (closed loop, zero think time)."""


def read(run):
    return run.window_s / run.cells if run.cells else None

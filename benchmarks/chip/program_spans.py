"""The program's own spans and counters (``repro.spans``) over the traced
window, for the readers under ``metrics/``.

``runner.run`` starts the profiler just before the window and stops it
just after, and the program records exactly while the profiler runs, so
the recorder's last window is the traced window.  A program without the
recorder gives nothing to read: every function here then returns None.
"""
from __future__ import annotations


def window() -> dict | None:
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.window()


def span_seconds(run, name: str) -> float | None:
    """Mean seconds per cell in the program span ``name``."""
    w = window()
    if w is None or not run.cells:
        return None
    return w["spans"].get(name, {}).get("seconds", 0.0) / run.cells


def counted(run, name: str, scale: float = 1.0) -> float | None:
    """Mean count per cell of the program counter ``name``, times
    ``scale``."""
    w = window()
    if w is None or not run.cells:
        return None
    return w["counters"].get(name, 0) * scale / run.cells

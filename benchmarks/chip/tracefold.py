"""From a profiler trace to device busy time, kernel time and a breakdown.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two things: every operation on each device's ``XLA Ops`` line, named
``kind instruction`` (``op_label``), and the benchmark's own host spans
(``bench.*`` trace annotations).  ``reduce``
works on that plain form, so it can be checked on a small recorded trace.

- The window is the host span ``bench.window``.
- Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices; the idle share is one minus busy over
  the window.
- Kernel time (``kernel_seconds``) is the sum of the durations, inside the
  window, of the operations whose name contains one of the given kernel
  names.
- The breakdown lists the operations that took most time and the longest
  idle gaps, each gap labelled by the innermost benchmark span the host was
  in at its middle.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
TOP = 10


_KIND = re.compile(r"\s([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """``kind instruction`` from an XLA operation's full text, e.g.
    ``custom-call _batched_lanes.1`` from ``%_batched_lanes.1 = u32[..]
    custom-call(...)``; a name that is not such text is kept whole."""
    head, sep, rest = name.partition(" = ")
    kind = _KIND.search(" " + rest) if sep else None
    return f"{kind.group(1)} {head.lstrip('%')}" if kind else name


def extract(trace_dir: str) -> dict:
    """{"device": {plane: [[name, start_ns, dur_ns], ...]},
        "host": [[name, start_ns, dur_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.setdefault(plane.name, []).extend(
                        [op_label(e.name), e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"device": device, "host": host}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _window(events: dict) -> tuple[float, float]:
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return max(windows, key=lambda w: w[1] - w[0])


def _clipped(ops, w0, w1):
    return [(max(s, w0), min(s + d, w1), n) for n, s, d in ops
            if s < w1 and s + d > w0]


def kernel_seconds(events: dict, kernels) -> float:
    """Device seconds inside the window of the operations whose name
    contains one of ``kernels``, summed over the devices."""
    w0, w1 = _window(events)
    return sum(e - s for ops in events["device"].values()
               for s, e, n in _clipped(ops, w0, w1)
               if any(k in n for k in kernels)) / 1e9


def reduce(events: dict) -> dict:
    """Busy and window seconds, idle share and the breakdown."""
    w0, w1 = _window(events)
    spans = [(n, s, s + d) for n, s, d in events["host"]
             if n != WINDOW and s < w1 and s + d > w0]
    if not events["device"]:
        raise ValueError("no device operations in the trace")

    busy, by_op, gaps = [], {}, []
    for plane, ops in sorted(events["device"].items()):
        clipped = _clipped(ops, w0, w1)
        merged = _union((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in clipped:
            by_op[n] = by_op.get(n, 0.0) + (e - s)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a)

    def label(a, b):
        mid = (a + b) / 2
        inside = [(e - s, n) for n, s, e in spans if s <= mid < e]
        return min(inside)[1] if inside else "none"

    window_ns = w1 - w0
    busy_ns = sum(busy) / len(busy)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label(a, b), g / 1e9] for g, a, b in
                      sorted(gaps, reverse=True)[:TOP]],
    }

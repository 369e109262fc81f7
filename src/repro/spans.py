"""Spans and counters of the migration path, on the profiler's clock.

``span(name, **meta)`` marks a step where its work happens.  While
recording, it enters ``jax.profiler.TraceAnnotation(name, **meta)``, so
the step lands in the profiler's trace beside the device operations, and
it adds its wall seconds and one call to the totals of ``name``.  Spans
nest per thread: each knows its parent.  ``count(name, n)`` adds to a
counter; counters always count.

Recording is on while a JAX profiler session is active, or inside a
``recording()`` scope.  A recording window opens at the first span, count
or read after recording turns on: the span totals start from zero and the
counters take a baseline.  It closes at the first such call after
recording turns off, and ``window()`` then returns what it held.  With
recording off a span costs one check of the profiler and records nothing.

While a window is open, every program JAX lowers (one
``/jax/core/compile/jaxpr_to_mlir_module_duration`` event) counts as a
lowering under the innermost open span's name, which says which step
recompiled.

Only totals are kept: the events themselves are in the profiler's trace.
This module depends on nothing else of ``repro``, so that the kernels can
import it.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import defaultdict
from contextlib import contextmanager, nullcontext

import jax

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
NO_SPAN = "none"        # where a lowering happens outside every span

_profiling = jax.profiler.TraceAnnotation.is_enabled
_OFF = nullcontext()


class Recorder:
    """Span totals and counters of one process (see the module's doc)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._forced = 0                 # open ``recording()`` scopes
        self._open = False
        self._listening = False
        self._counters: dict[str, int] = defaultdict(int)
        self._baseline: dict[str, int] = {}
        self._seconds: dict[str, float] = defaultdict(float)
        self._calls: dict[str, int] = defaultdict(int)
        self._parents: dict[str, set] = defaultdict(set)
        self._lowerings: dict[str, int] = defaultdict(int)
        self._last: dict | None = None

    # -- the window ------------------------------------------------------
    def _check(self) -> bool:
        """Whether recording is on; opens or closes the window on a change."""
        on = self._forced > 0 or _profiling()
        if on is not self._open:
            self._flip(on)
        return on

    def _flip(self, on: bool) -> None:
        with self._lock:
            if on is self._open:
                return
            if on:
                if not self._listening:
                    jax.monitoring.register_event_duration_secs_listener(
                        self._on_event)
                    self._listening = True
                for totals in (self._seconds, self._calls, self._parents,
                               self._lowerings):
                    totals.clear()
                self._baseline = dict(self._counters)
            else:
                self._last = self._snapshot()
            self._open = on

    def _snapshot(self) -> dict:
        return {
            "spans": {n: {"seconds": self._seconds[n],
                          "calls": self._calls[n],
                          "parents": sorted(self._parents[n])}
                      for n in self._calls},
            "counters": {n: v - self._baseline.get(n, 0)
                         for n, v in self._counters.items()},
            "lowerings": dict(self._lowerings),
        }

    def window(self) -> dict | None:
        """The last window (the open one while recording): ``spans`` (name
        -> seconds, calls, parents), ``counters`` (name -> delta over the
        window) and ``lowerings`` (span name -> programs lowered).  None
        before any window has opened."""
        if self._check():
            with self._lock:
                return self._snapshot()
        return self._last

    @contextmanager
    def recording(self):
        """Record inside this scope, profiler or not."""
        with self._lock:
            self._forced += 1
        try:
            self._check()
            yield self
        finally:
            with self._lock:
                self._forced -= 1
            self._check()

    # -- spans and counters ------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **meta):
        if not self._check():
            return _OFF
        return _Span(self, name, meta)

    def count(self, name: str, n: int = 1) -> None:
        self._check()
        with self._lock:
            self._counters[name] += int(n)

    def total(self, name: str) -> int:
        """A counter's count since the process started."""
        return self._counters.get(name, 0)

    def _add(self, name: str, parent: str | None, seconds: float) -> None:
        if not self._check():
            return                # the window closed while the span ran
        with self._lock:
            self._seconds[name] += seconds
            self._calls[name] += 1
            if parent is not None:
                self._parents[name].add(parent)

    def _on_event(self, event: str, seconds: float, **kw) -> None:
        if event != LOWERING_EVENT or not self._check():
            return
        stack = self._stack()
        with self._lock:
            self._lowerings[stack[-1] if stack else NO_SPAN] += 1


class _Span:
    __slots__ = ("rec", "name", "meta", "parent", "annotation", "t0")

    def __init__(self, rec: Recorder, name: str, meta: dict):
        self.rec, self.name, self.meta = rec, name, meta

    def __enter__(self):
        stack = self.rec._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.annotation = jax.profiler.TraceAnnotation(self.name, **self.meta)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        self.rec._stack().pop()
        self.rec._add(self.name, self.parent, dt)
        return False


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
total = RECORDER.total
window = RECORDER.window
recording = RECORDER.recording


def spanned(name: str):
    """Decorator: every call of the function is one span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap

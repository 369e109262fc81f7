"""The recorder of spans and counters (``repro.spans``): it records only
while the profiler runs or inside ``recording()``, keeps each span's parent
and counts lowerings under the innermost span."""
import sys
import threading

import jax
import numpy as np

from repro import spans


def _profile(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)


def test_spans_record_nothing_while_recording_is_off():
    before = spans.window()
    with spans.span("test.off") as s:
        pass
    assert s is None                      # the shared no-op context
    assert spans.window() is before
    with spans.recording():
        pass
    assert "test.off" not in spans.window()["spans"]


def test_recording_scope_opens_and_closes_a_window():
    with spans.recording():
        with spans.span("test.scope"):
            pass
        with spans.span("test.scope"):
            pass
        assert spans.window()["spans"]["test.scope"]["calls"] == 2
    w = spans.window()
    assert w["spans"]["test.scope"]["calls"] == 2
    assert w["spans"]["test.scope"]["seconds"] >= 0.0
    with spans.span("test.scope"):        # off again: the window is kept
        pass
    assert spans.window() is w


def test_each_profiler_session_is_a_window_of_its_own(tmp_path):
    _profile(tmp_path / "one")
    with spans.span("test.first"):
        pass
    jax.profiler.stop_trace()
    w = spans.window()
    assert w["spans"]["test.first"]["calls"] == 1

    _profile(tmp_path / "two")
    with spans.span("test.second"):
        pass
    jax.profiler.stop_trace()
    w = spans.window()
    assert set(w["spans"]) == {"test.second"}     # the totals were reset


def test_a_span_keeps_its_parent_across_nesting():
    with spans.recording():
        with spans.span("test.outer"):
            with spans.span("test.middle"):
                with spans.span("test.inner"):
                    pass
            with spans.span("test.inner"):
                pass
    w = spans.window()["spans"]
    assert w["test.outer"]["parents"] == []
    assert w["test.middle"]["parents"] == ["test.outer"]
    assert w["test.inner"]["parents"] == ["test.middle", "test.outer"]
    assert w["test.inner"]["calls"] == 2


def test_a_new_shape_counts_one_lowering_under_the_innermost_span():
    f = jax.jit(lambda x: x * 3 + 1)
    x = np.ones((7, 13), np.float32)     # host input: nothing lowers for it
    with spans.recording():
        with spans.span("test.jit"):
            f(x).block_until_ready()
            f(x).block_until_ready()      # cached: no second lowering
    assert spans.window()["lowerings"] == {"test.jit": 1}
    with spans.recording():
        f(x).block_until_ready()
    assert spans.window()["lowerings"] == {}


def test_counters_count_always_and_keep_their_baseline_across_a_window():
    start = spans.total("test.count")
    spans.count("test.count", 5)
    with spans.recording():
        spans.count("test.count", 2)
        spans.count("test.count")
    spans.count("test.count", 4)          # after the window closed
    assert spans.window()["counters"]["test.count"] == 3
    assert spans.total("test.count") == start + 12


def test_threads_lose_no_count_and_keep_their_own_parents():
    threads, rounds = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for _ in range(rounds):
                with spans.span(f"test.thread{i % 2}"):
                    with spans.span("test.leaf"):
                        spans.count("test.threads")
        with spans.recording():
            pool = [threading.Thread(target=work, args=(i,))
                    for i in range(threads)]
            for t in pool:
                t.start()
            for t in pool:
                t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    w = spans.window()
    assert w["counters"]["test.threads"] == threads * rounds
    assert w["spans"]["test.leaf"]["calls"] == threads * rounds
    assert w["spans"]["test.leaf"]["parents"] == ["test.thread0",
                                                  "test.thread1"]
    assert w["spans"]["test.thread0"]["parents"] == []

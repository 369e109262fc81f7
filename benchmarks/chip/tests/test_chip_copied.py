"""The reader of the program's ``copied_bytes`` counter: a number after a
tiny cell, nothing before any window, and nothing from a program that does
not count host copies."""
import math
import os
import types

import chip_tiny
import pytest
import runner

from repro import spans


def _read(cells):
    reader = runner._load_module(
        os.path.join(chip_tiny.CHIP, "metrics", "copied_MB.py"),
        "bench_metric_copied_MB")
    return reader.read(types.SimpleNamespace(cells=cells))


@pytest.mark.parametrize("workload", ["mamba2_370m_train.cycle",
                                      "spacenet7_kmeans.refilter"])
def test_copied_MB_reads_the_host_copies_of_a_cell(workload):
    with spans.recording():
        r = chip_tiny.run(workload)
        pulled = spans.window()["counters"].get("pull_bytes", 0)
    assert r["correct"] is True
    value = _read(r["_info"]["cells"])
    assert isinstance(value, float) and math.isfinite(value) and value > 0
    # at least the restore of every pulled byte is a copy
    assert value * r["_info"]["cells"] >= pulled * 1e-6


@pytest.mark.parametrize("window", [
    None, {"spans": {}, "counters": {"pull_bytes": 10}, "lowerings": {}}])
def test_copied_MB_is_none_without_the_counter(monkeypatch, window):
    monkeypatch.setattr(spans, "window", lambda: window)
    assert _read(3) is None

"""The readers of the program's ``kept_bytes`` and ``held_bytes``
counters: a number after a tiny cell, where the eval cycle keeps the
weights back and B's chunks are held at home, and nothing from a program
without the counters."""
import math
import os
import types

import chip_tiny
import pytest
import runner

from repro import spans

READERS = ("kept_MB", "held_MB")


def _read(name, cells):
    reader = runner._load_module(
        os.path.join(chip_tiny.CHIP, "metrics", f"{name}.py"),
        f"bench_metric_{name}")
    return reader.read(types.SimpleNamespace(cells=cells))


@pytest.mark.parametrize("workload", ["mamba2_370m_train.eval",
                                      "spacenet7_kmeans.refilter"])
def test_kept_and_held_read_after_a_cell(workload):
    with spans.recording():
        r = chip_tiny.run(workload)
    assert r["correct"] is True
    values = {name: _read(name, r["_info"]["cells"]) for name in READERS}
    assert all(isinstance(v, float) and math.isfinite(v) and v >= 0
               for v in values.values()), values
    # eval leaves the weights as they are, and B's scenes return home to
    # chunks already there
    key = "kept_MB" if workload.startswith("mamba2") else "held_MB"
    assert values[key] > 0


@pytest.mark.parametrize("window", [
    None, {"spans": {}, "counters": {"pull_bytes": 10}, "lowerings": {}}])
def test_kept_and_held_are_none_without_the_counters(monkeypatch, window):
    monkeypatch.setattr(spans, "window", lambda: window)
    assert all(_read(name, 3) is None for name in READERS)

"""The reader of the program's ``placed_bytes`` counter: after a tiny cell
it reads every byte pulled from a device and placed back on one by
restore, 0 where the state lives on the host, and nothing from a program
without the counter."""
import os
import types

import chip_tiny
import pytest
import runner

from repro import spans


def _read(cells):
    reader = runner._load_module(
        os.path.join(chip_tiny.CHIP, "metrics", "placed_MB.py"),
        "bench_metric_placed_MB")
    return reader.read(types.SimpleNamespace(cells=cells))


@pytest.mark.parametrize("workload", ["mamba2_370m_train.cycle",
                                      "mamba2_370m_train.eval",
                                      "spacenet7_kmeans.refilter"])
def test_placed_MB_reads_what_restore_put_on_a_device(workload):
    with spans.recording():
        r = chip_tiny.run(workload)
        pulled = spans.window()["counters"].get("pull_bytes", 0)
    assert r["correct"] is True
    cells = r["_info"]["cells"]
    value = _read(cells)
    assert isinstance(value, float)
    # each device leaf a migration captured comes back on a device; B's
    # state is host arrays from the start
    assert value * cells == pytest.approx(pulled * 1e-6, rel=1e-9)
    assert (value > 0) is workload.startswith("mamba2")


@pytest.mark.parametrize("window", [
    None, {"spans": {}, "counters": {"pull_bytes": 10}, "lowerings": {}}])
def test_placed_MB_is_none_without_the_counter(monkeypatch, window):
    monkeypatch.setattr(spans, "window", lambda: window)
    assert _read(3) is None

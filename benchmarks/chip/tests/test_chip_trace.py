"""The reduction from a profiler trace to busy time, idle share, kernel
time and the breakdown."""
import json
import os

import chip_tiny  # noqa: F401  (puts the benchmark on sys.path)
import jax
import jax.numpy as jnp
import pytest
import tracefold

MS = 1_000_000   # nanoseconds


def _events():
    # a 100 ms window; ops at [10, 30) and [20, 40) overlap, [60, 70) alone,
    # and one op straddles the window's end
    return {
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.migrate", 40 * MS, 30 * MS],
                 ["bench.capture", 45 * MS, 10 * MS],
                 ["bench.exec", 75 * MS, 25 * MS]],
        "device": {"/device:TPU:0": [
            ["fusion.1", 10 * MS, 20 * MS],
            ["_hash_kernel.3", 20 * MS, 20 * MS],
            ["_hash_kernel.3", 60 * MS, 10 * MS],
            ["copy.2", 95 * MS, 20 * MS]]},
    }


def test_busy_is_the_union_of_operations_inside_the_window():
    r = tracefold.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.030 + 0.010 + 0.005)
    assert r["idle_share"] == pytest.approx(1 - 0.045 / 0.100)


def test_kernel_time_sums_matching_operations_inside_the_window():
    assert tracefold.kernel_seconds(_events(), ("_hash_kernel",)) == \
        pytest.approx(0.030)
    assert tracefold.kernel_seconds(_events(), ("no_such_kernel",)) == 0.0


def test_breakdown_ranks_operations_and_labels_gaps_by_the_host_span():
    r = tracefold.reduce(_events())
    assert r["device_ops"][0] == ["_hash_kernel.3", pytest.approx(0.030)]
    # gaps: [0,10) none, [40,60) capture at its middle 50, [70,95) exec
    gaps = {label: s for label, s in r["idle_gaps"]}
    assert gaps == {"none": pytest.approx(0.010),
                    "bench.capture": pytest.approx(0.020),
                    "bench.exec": pytest.approx(0.025)}
    assert [g for _, g in r["idle_gaps"]] == sorted(
        (g for _, g in r["idle_gaps"]), reverse=True)


def test_recorded_chip_trace():
    path = os.path.join(os.path.dirname(__file__), "data", "chip_trace.json")
    with open(path) as f:
        rec = json.load(f)
    r = tracefold.reduce(rec["events"])
    for key in ("window_s", "busy_s", "idle_share"):
        assert r[key] == pytest.approx(rec["expected"][key])
    assert tracefold.kernel_seconds(rec["events"], rec["kernels"]) == \
        pytest.approx(rec["expected"]["kernel_s"])
    assert 0 < r["busy_s"] <= r["window_s"]


def test_extract_keeps_the_benchmark_spans(tmp_path):
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracefold.WINDOW):
        with jax.profiler.TraceAnnotation("bench.exec"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tracefold.extract(str(tmp_path))
    names = [n for n, _, _ in ev["host"]]
    assert tracefold.WINDOW in names and "bench.exec" in names
    assert all(n.startswith("bench.") for n in names)

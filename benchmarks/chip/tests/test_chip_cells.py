"""Each cell of the chip benchmark builds its notebook from the seed at a
tiny size, runs set-up, warm-up and a short window through the runtime,
and passes the comparison with the plain reference."""
import chip_tiny
import pytest


@pytest.mark.parametrize("workload", chip_tiny.WORKLOADS)
def test_cell_round_trips_through_the_reference(workload):
    r = chip_tiny.run(workload)
    info = r["_info"]
    assert r["failed"] == 0, info["errors"]
    assert {k: c["value"] for k, c in r["checks"].items()} == {
        "restore_bad": 0, "delta_missed": 0, "kernel_bad": 0,
        "output_bad": 0}
    assert r["correct"] is True
    # the comparison had something to compare, and the window compiled
    # nothing
    assert info["restore_leaves"] > 0 and info["output_leaves"] > 0
    assert info["kernel_digests"] >= 2
    assert info["window_compiles"] == 0
    assert r["attempted"] == info["cells"] >= 2
    assert set(r["metrics"]) >= {"cell_s", "host_peak_GB", "setup_s"}
    assert list(r)[-2:] == ["checks", "_info"]

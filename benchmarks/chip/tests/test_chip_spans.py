"""The readers of the program's own spans and counters: each returns a
number after a tiny cell, and the counters equal what the leaves and
payloads of one capture say they must be."""
import math
import os
import types

import chip_tiny
import jax.numpy as jnp
import numpy as np
import pytest
import runner

from repro import spans
from repro.core import ExecutionState, StateReducer

READERS = ("pickle_s", "pull_s", "payload_s", "encode_s", "hash_stage_s",
           "hash_upload_s", "hash_wait_s", "assemble_s", "pull_MB",
           "upload_MB", "widened_MB", "host_syncs", "lowerings")
BLOCK = 1024            # elements (or bytes) per hashed block row


def _read(name, cells):
    reader = runner._load_module(
        os.path.join(chip_tiny.CHIP, "metrics", f"{name}.py"),
        f"bench_metric_{name}")
    return reader.read(types.SimpleNamespace(cells=cells))


@pytest.mark.parametrize("workload", ["mamba2_370m_train.cycle",
                                      "spacenet7_kmeans.refilter"])
def test_every_program_reader_returns_a_number(workload):
    with spans.recording():
        r = chip_tiny.run(workload)
    assert r["correct"] is True
    cells = r["_info"]["cells"]
    values = {name: _read(name, cells) for name in READERS}
    assert all(isinstance(v, (int, float)) and math.isfinite(v)
               for v in values.values()), values
    # every cell captures and restores, so these steps held time
    assert values["pickle_s"] > 0 and values["assemble_s"] > 0
    assert values["host_syncs"] > 0 and values["widened_MB"] > 0


def test_readers_find_nothing_before_any_window(monkeypatch):
    monkeypatch.setattr(spans, "window", lambda: None)
    assert all(_read(name, 3) is None for name in READERS)


def _rows(n: int) -> int:
    return -(-n // BLOCK)


def test_counters_are_exact_for_one_delta_and_capture():
    ns = {
        "w": jnp.arange(3000, dtype=jnp.float32).reshape(30, 100),
        "b": jnp.ones(5000, jnp.bfloat16),
        "h": np.arange(2500, dtype=np.float32),
        "hb": np.ones(4100, dtype=jnp.bfloat16),
        "hd": np.linspace(0, 1, 700),                       # float64
        "tree": {"a": jnp.ones(10), "n": np.zeros(3, np.int32)},
        "obj": [1, 2, "x"],
    }
    device = [ns["w"], ns["b"], ns["tree"]["a"]]
    host = [ns["h"], ns["hb"], ns["hd"], ns["tree"]["n"]]
    # uint32 lanes each leaf hashes as: device leaves widen every element
    # on the device; host leaves of 2 or 8 bytes are viewed as raw uint32
    lanes = {id(a): a.size for a in device + host}
    lanes[id(ns["hb"])] = ns["hb"].nbytes // 4
    lanes[id(ns["hd"])] = ns["hd"].nbytes // 4
    payloads = [a.nbytes for a in device + host]

    red = StateReducer(codec="none")
    state = ExecutionState(ns)
    with spans.recording():
        send, _, here = red.delta_names(state, set(ns), {})
        red.serialize_names(state, send, digests=here)
    counters = spans.window()["counters"]

    # delta: one batch over every leaf, host leaves staged and uploaded;
    # capture: one batch over every payload's bytes, uploaded as uint8
    assert send == set(ns)
    assert counters["pull_bytes"] == sum(a.nbytes for a in device)
    assert counters["upload_bytes"] == (
        sum(_rows(lanes[id(a)]) * BLOCK * 4 for a in host)
        + sum(_rows(n) * BLOCK for n in payloads))
    assert counters["widened_bytes"] == (
        sum(_rows(lanes[id(a)]) * BLOCK * 4 for a in device + host)
        + sum(_rows(n) * BLOCK * 4 for n in payloads))
    assert counters["host_syncs"] == 2

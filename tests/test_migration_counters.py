"""The migration's ``kept_bytes`` and ``held_bytes`` counters: exact on a
namespace with an unchanged array, a changed one and one whose chunks the
destination already holds."""
import pickle

import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import ExecutionEnvironment, MigrationEngine, StateReducer


def test_kept_and_held_bytes_are_exact():
    local, remote = ExecutionEnvironment("local"), ExecutionEnvironment("remote")
    same = np.arange(100_000, dtype=np.float32)          # two 256 KiB chunks
    ns = {"same": same, "changed": np.ones(50_000, np.float32),
          "dev": jnp.arange(1000, dtype=jnp.float32), "conf": {"lr": 0.1}}
    local.state.update(ns)
    eng = MigrationEngine(StateReducer("none"))
    eng.migrate(local, remote, names=set(ns))
    # one changed, one new name whose bytes the destination's store holds
    local.state.update({"changed": np.full(50_000, 2.0, np.float32),
                        "copy": same.copy()})
    with spans.recording():
        res = eng.migrate(local, remote, names=set(ns) | {"copy"})
    counters = spans.window()["counters"]
    assert set(res.names) == {"changed", "copy"}
    conf = len(pickle.dumps({"lr": 0.1}, protocol=pickle.HIGHEST_PROTOCOL))
    assert counters["kept_bytes"] == same.nbytes + 4000 + conf
    assert counters["held_bytes"] == same.nbytes
    np.testing.assert_array_equal(remote.state["copy"], same)


def test_nothing_kept_or_held_on_a_first_migration():
    local, remote = ExecutionEnvironment("local"), ExecutionEnvironment("remote")
    local.state.update({"a": np.arange(10.0), "b": [1, 2]})
    eng = MigrationEngine(StateReducer("none"))
    with spans.recording():
        eng.migrate(local, remote, names={"a", "b"})
    counters = spans.window()["counters"]
    assert counters["kept_bytes"] == 0 and counters["held_bytes"] == 0

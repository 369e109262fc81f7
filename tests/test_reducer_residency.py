"""Residency through capture and restore: an array captured from a device
comes back as a ``jax.Array`` on the default device, bit for bit, and a
host array comes back as a new numpy array.  The ``placed_bytes`` counter
says how many bytes restore put on a device."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import (
    ExecutionEnvironment, ExecutionState, MigrationEngine, StateReducer,
)
from repro.core import wire
from repro.core.chunkstore import MemoryChunkStore
from repro.core.reducer import CODECS

CHUNK = 4096            # small chunks: every array below spans several
LOSSLESS = [c for c in CODECS if c != "quant8+zstd"]

_rng = np.random.default_rng(11)
HOST = {
    "bfloat16": _rng.standard_normal(5000).astype(jnp.bfloat16),
    "float32": _rng.standard_normal((60, 70)).astype(np.float32),
    "int32": _rng.integers(-2**31, 2**31 - 1, 7000, dtype=np.int32),
    "bool": _rng.random((90, 50)) > 0.5,
}
DEVICE = {k: jnp.asarray(v) for k, v in HOST.items()}
DEVICE["tree"] = {"w": DEVICE["bfloat16"],
                  "layers": [DEVICE["float32"], DEVICE["int32"]],
                  "mask": DEVICE["bool"]}


def _bits(a) -> tuple:
    a = np.asarray(a)
    return a.shape, str(a.dtype), np.ascontiguousarray(a).tobytes()


def _round_trip(obj, codec, source):
    red = StateReducer(codec=codec, chunk_bytes=CHUNK)
    ser = red.serialize_names(ExecutionState({"x": obj}), {"x"})
    store = None
    if source == "store":
        store = MemoryChunkStore()
        store.put_many(ser.chunks)
        ser.chunks = {}
    return ser, red.deserialize(ser, chunk_store=store)["x"]


@pytest.mark.parametrize("source", ["capture", "store"])
@pytest.mark.parametrize("codec", LOSSLESS)
@pytest.mark.parametrize("kind", DEVICE)
def test_a_device_leaf_comes_back_on_the_default_device(kind, codec, source):
    obj = DEVICE[kind]
    _, out = _round_trip(obj, codec, source)
    got, want = jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(obj)
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(obj)
    for g, w in zip(got, want):
        assert isinstance(g, jax.Array)
        assert g.devices() == {jax.devices()[0]}
        assert _bits(g) == _bits(w)


@pytest.mark.parametrize("kind", ["bfloat16", "float32"])
def test_a_quantized_device_leaf_comes_back_on_the_default_device(kind):
    a = DEVICE[kind]
    ser, out = _round_trip(a, "quant8+zstd", "capture")
    assert ser.blobs["x"].arrays[0]["quant"]
    assert isinstance(out, jax.Array)
    assert out.devices() == {jax.devices()[0]}
    assert out.shape == a.shape and out.dtype == a.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(a, np.float32), atol=0.05)


@pytest.mark.parametrize("source", ["capture", "store"])
@pytest.mark.parametrize("codec", LOSSLESS)
@pytest.mark.parametrize("kind", HOST)
def test_a_host_leaf_comes_back_as_its_own_numpy_array(kind, codec, source):
    a = HOST[kind]
    ser, out = _round_trip(a, codec, source)
    assert "device" not in ser.blobs["x"].arrays[0]
    assert type(out) is np.ndarray
    assert out.flags.writeable and out.flags.owndata
    assert not np.shares_memory(out, a)
    assert _bits(out) == _bits(a)


@pytest.mark.parametrize("kind", ["bfloat16", "float32"])
def test_a_quantized_host_leaf_comes_back_on_the_host(kind):
    a = HOST[kind]
    ser, out = _round_trip(a, "quant8+zstd", "capture")
    assert ser.blobs["x"].arrays[0]["quant"]
    assert "device" not in ser.blobs["x"].arrays[0]
    assert type(out) is np.ndarray
    assert out.shape == a.shape and out.dtype == a.dtype


def test_the_device_mark_crosses_the_wire_and_host_manifests_do_not_change():
    red = StateReducer(codec="none", chunk_bytes=CHUNK)
    ser = red.serialize_names(
        ExecutionState({"d": DEVICE["float32"], "h": HOST["float32"]}),
        {"d", "h"})
    frame = wire.manifest_frame(ser)
    back = wire.parse_manifest(frame)[0]
    assert back.blobs["d"].arrays[0]["device"] is True
    assert "device" not in back.blobs["h"].arrays[0]
    assert back.blobs == ser.blobs
    back.chunks = ser.chunks
    out = red.deserialize(back)
    assert isinstance(out["d"], jax.Array) and type(out["h"]) is np.ndarray
    # a host-only manifest is the same frame as before the mark existed
    host_only = red.serialize_names(ExecutionState({"h": HOST["float32"]}),
                                    {"h"})
    assert b'"device"' not in bytes(wire.manifest_frame(host_only).payload)


def test_unchanged_device_state_returns_home_with_nothing_sent():
    home, remote = ExecutionEnvironment("home"), ExecutionEnvironment("remote")
    state = {"w": DEVICE["bfloat16"], "opt": DEVICE["tree"]}
    home.state.update(state)
    eng = MigrationEngine(StateReducer("none", chunk_bytes=CHUNK))
    first = eng.migrate(home, remote, names=set(state))
    assert set(first.names) == set(state)
    assert isinstance(remote.state["w"], jax.Array)
    back = eng.migrate(remote, home, names=set(state))
    # the restored bf16 leaf digests as the device leaf it was, so the
    # delta finds every name known at home
    assert back.noop and back.names == () and back.nbytes == 0
    for n in state:
        for g, w in zip(jax.tree_util.tree_leaves(home.state[n]),
                        jax.tree_util.tree_leaves(state[n])):
            assert g is w


def test_placed_bytes_counts_the_device_leaves_exactly():
    ns = {"d": DEVICE["bfloat16"], "tree": DEVICE["tree"],
          "h": HOST["float32"], "conf": {"lr": 0.1, "host": HOST["int32"]}}
    red = StateReducer(codec="zstd", chunk_bytes=CHUNK)
    ser = red.serialize_names(ExecutionState(ns), set(ns))
    with spans.recording():
        out = red.deserialize(ser)
    w = spans.window()
    device = [ns["d"], *jax.tree_util.tree_leaves(ns["tree"])]
    assert w["counters"]["placed_bytes"] == sum(a.nbytes for a in device)
    assert w["spans"]["reducer.place"]["calls"] == len(device)
    assert type(out["conf"]["host"]) is np.ndarray
    with spans.recording():
        red.deserialize(red.serialize_names(ExecutionState(ns), {"h"}))
    assert spans.window()["counters"]["placed_bytes"] == 0


def test_a_dtype_this_process_cannot_hold_on_a_device_stays_on_the_host():
    with jax.enable_x64(True):
        a = jnp.arange(3000, dtype=jnp.float64) / 7
        red = StateReducer(codec="none", chunk_bytes=CHUNK)
        ser = red.serialize_names(ExecutionState({"x": a}), {"x"})
        want = _bits(a)
    assert ser.blobs["x"].arrays[0]["device"] is True
    out = red.deserialize(ser)["x"]
    assert type(out) is np.ndarray and _bits(out) == want


def test_a_sharded_leaf_is_restored_whole_on_the_host():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ExecutionState, StateReducer
        mesh = jax.make_mesh((2,), ("x",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        a = jax.device_put(jnp.arange(4096.0).reshape(64, 64),
                           NamedSharding(mesh, P("x")))
        red = StateReducer(codec="none")
        ser = red.serialize_names(ExecutionState({"a": a}), {"a"})
        out = red.deserialize(ser)["a"]
        assert "device" not in ser.blobs["a"].arrays[0]
        assert type(out) is np.ndarray
        np.testing.assert_array_equal(out, np.asarray(a))
        print("OK")
    """
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))), "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0 and r.stdout.strip() == "OK", r.stderr

"""State reducer: serialization codecs, deltas, digests (paper §II-D)."""
import numpy as np
import pytest
import jax.numpy as jnp
from _hyp_compat import given, settings, st

from repro.core import ExecutionState, SerializationFailure, StateReducer
from repro.core.reducer import CODECS


def _roundtrip(objs, codec):
    red = StateReducer(codec=codec)
    st_ = ExecutionState(dict(objs))
    ser = red.serialize_names(st_, list(objs))
    return red.deserialize(ser), ser


@pytest.mark.parametrize("codec", CODECS)
def test_roundtrip_all_codecs(codec):
    objs = {
        "arr": np.arange(4000, dtype=np.float32).reshape(40, 100),
        "jarr": jnp.linspace(0, 1, 256, dtype=jnp.float32),
        "tree": {"a": np.ones(7), "b": [np.zeros(3), 5, "text"]},
        "scalar": 42,
        "string": "hello",
    }
    out, ser = _roundtrip(objs, codec)
    assert out["scalar"] == 42 and out["string"] == "hello"
    lossless = codec != "quant8+zstd"
    if lossless:
        np.testing.assert_array_equal(out["arr"], objs["arr"])
        np.testing.assert_array_equal(np.asarray(out["jarr"]), np.asarray(objs["jarr"]))
    else:
        # blockwise int8: relative error bounded by scale/127
        err = np.abs(out["arr"] - objs["arr"])
        bound = np.abs(objs["arr"]).max() / 127 + 1e-6
        assert err.max() <= bound
    assert ser.nbytes > 0


def test_compression_reduces_size():
    x = np.zeros((512, 512), np.float32)  # highly compressible
    _, raw = _roundtrip({"x": x}, "none")
    _, z = _roundtrip({"x": x}, "zlib")
    assert z.nbytes < raw.nbytes / 10


def test_function_roundtrip_rebinds_globals():
    src_ns = {}
    exec("scale = 3.0\ndef f(v):\n    return v * scale", src_ns)
    red = StateReducer(codec="zlib")
    ser = red.serialize_names(ExecutionState(src_ns), ["f", "scale"])
    target = {"scale": 100.0}
    out = red.deserialize(ser, target_ns=target)
    target.update(out)
    # migrated function must resolve `scale` in the *destination* namespace
    assert target["f"](2.0) == 2.0 * 3.0


def test_serialization_failure_raised():
    import threading
    red = StateReducer()
    with pytest.raises(SerializationFailure):
        red.serialize_names(ExecutionState({"bad": threading.Lock()}), ["bad"])


def test_on_error_skip_roundtrips_serializable_names():
    """on_error="skip": unserializable names stay behind, everything else
    round-trips intact (the return-migration path)."""
    import threading
    red = StateReducer(codec="zlib")
    objs = {"bad": threading.Lock(),
            "arr": np.arange(100, dtype=np.float32),
            "note": "still travels"}
    ser = red.serialize_names(ExecutionState(objs), list(objs),
                              on_error="skip")
    assert ser.skipped == ("bad",)
    assert set(ser.blobs) == {"arr", "note"}
    assert "bad" not in ser.digests          # skipped names have no digest
    out = red.deserialize(ser)
    np.testing.assert_array_equal(out["arr"], objs["arr"])
    assert out["note"] == "still travels"


def test_delta_names_semantics():
    red = StateReducer()
    s = ExecutionState({"a": np.arange(10), "b": np.zeros(5), "c": 1})
    send, dead, here = red.delta_names(s, {"a", "b", "c"}, known={})
    assert send == {"a", "b", "c"} and not dead
    known = dict(here)
    s["a"] = np.arange(10) + 1          # changed
    s.drop(["b"])                        # deleted
    send, dead, _ = red.delta_names(s, {"a", "c"}, known)
    assert send == {"a"}
    assert dead == {"b"}


@given(st.lists(st.floats(-1e6, 1e6, allow_nan=False), min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_digest_deterministic_and_sensitive(vals):
    red = StateReducer()
    a = np.asarray(vals, np.float32)
    d1, d2 = red.digest(a), red.digest(a.copy())
    assert d1 == d2
    b = a.copy()
    b[0] = b[0] + 1.0 if np.isfinite(b[0] + 1.0) else 0.5
    if not np.array_equal(a, b):
        assert red.digest(b) != d1


def test_digest_keeps_all_64_bits_of_wide_dtypes():
    """With x64 disabled jnp.asarray narrows int64/float64; the digest must
    still see every bit or a high-word change silently skips migration."""
    red = StateReducer()
    a = np.array([2**32, 5], dtype=np.int64)
    b = np.array([2**33, 5], dtype=np.int64)       # differs above bit 32
    assert red.digest(a) != red.digest(b)
    f = np.array([1.0, 2.0], dtype=np.float64)
    g = f.copy()
    g[0] += 1e-9                                   # lost in a float32 cast
    assert red.digest(f) != red.digest(g)
    z = np.array([1 + 2j, 3 + 4j], dtype=np.complex128)
    w = z.copy()
    w[1] = 3 + 5j
    assert red.digest(z) != red.digest(w)


@given(st.integers(1, 3), st.integers(1, 2049))
@settings(max_examples=30, deadline=None)
def test_quant_roundtrip_bounds(seed, n):
    from repro.kernels.quant_blockwise.ops import dequantize, quantize
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    q, s = quantize(x)
    y = dequantize(q, s, (n,), jnp.float32)
    # per-block bound: |err| <= blockmax/127 (+eps)
    assert float(jnp.max(jnp.abs(y - x))) <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6


def test_reduce_state_flag():
    ns = {}
    exec("import numpy as np\nbig = np.zeros((256,256))\nx = 1", ns)
    st_ = ExecutionState(ns)
    red_on = StateReducer(reduce_state=True)
    red_off = StateReducer(reduce_state=False)
    names_on, _, _ = red_on.reduce(st_, "y = x + 1")
    names_off, _, _ = red_off.reduce(st_, "y = x + 1")
    assert names_on == {"x"}
    assert "big" in names_off  # full state capture


def test_digest_handles_strided_complex128_views():
    """Non-contiguous wide leaves must digest by content, not by whatever
    bytes a raw view would alias."""
    red = StateReducer()
    base = np.arange(32, dtype=np.complex128) + 1j * np.arange(32)
    v = base[::2]                                   # strided view
    assert red.digest(v) == red.digest(np.ascontiguousarray(v))
    w = np.ascontiguousarray(v)
    w[3] = w[3].conjugate()                         # imaginary part only
    assert red.digest(w) != red.digest(v)


def test_digest_sees_imaginary_part_of_jax_complex_leaves():
    """jax complex leaves used to fall through an XLA convert that kept
    only the real part, so conjugation was invisible to the digest."""
    red = StateReducer()
    z = jnp.asarray(np.array([1 + 2j, 3 + 4j], np.complex64))
    assert red.digest(z) != red.digest(jnp.conj(z))
    z64 = jnp.asarray(np.array([1 + 2j, 3 + 4j]))
    assert red.digest(z64) != red.digest(jnp.conj(z64))


def test_digest_many_matches_per_object_digests():
    red = StateReducer()
    rng = np.random.default_rng(8)
    objs = {
        "a": rng.standard_normal(500).astype(np.float32),
        "b": jnp.asarray(rng.standard_normal(64), jnp.float32),
        "tree": {"x": rng.standard_normal(10), "y": [1, 2]},
        "host": "just a string",
        "wide": rng.integers(0, 2**40, 7).astype(np.int64),
    }
    singles = {n: red.digest(v) for n, v in objs.items()}
    assert red.digest_many(objs) == singles

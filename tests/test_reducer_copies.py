"""One host copy per byte through the chunk store: capture encodes chunks
from byte views of the array, restore fills a new array in place.  Chunks,
keys and manifests are the bytes the copying path made; an array captured
from a device is marked so, and comes back on a device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import ExecutionState, StateReducer
from repro.core.chunkstore import (
    MemoryChunkStore, array_chunk_digests_many, encode_chunk, split_chunks,
)
from repro.core.reducer import CODECS, _compress

CHUNK = 4096            # small chunks: every array below spans several

_rng = np.random.default_rng(7)
ARRAYS = {
    "bfloat16": _rng.standard_normal(5000).astype(jnp.bfloat16),
    "bfloat16_device": jnp.linspace(-3, 3, 5000, dtype=jnp.bfloat16),
    "bool": _rng.random(9000) > 0.5,
    "int64": _rng.integers(-2**62, 2**62, (40, 70), dtype=np.int64),
    "complex64": (_rng.standard_normal(3000)
                  + 1j * _rng.standard_normal(3000)).astype(np.complex64),
    "float32_device": jnp.arange(6000, dtype=jnp.float32).reshape(60, 100),
    "zero_d": np.array(3.25, np.float64),
    "empty": np.zeros((0, 5), np.float32),
    "fortran": np.asfortranarray(
        _rng.standard_normal((50, 60)).astype(np.float32)),
    "strided": _rng.integers(0, 1000, (80, 90), dtype=np.int32)[::3, 1::2],
}
LOSSLESS = [c for c in CODECS if c != "quant8+zstd"]


def _old_capture(a, codec):
    """The capture before payloads became views: ``tobytes`` of the host
    array, then ``bytes`` slices, each encoded.  A device array's meta
    says so."""
    meta = {"shape": a.shape, "dtype": str(a.dtype)}
    if isinstance(a, jax.Array):
        meta["device"] = True
    if codec == "quant8+zstd" and a.dtype in (np.dtype("float32"),
                                              np.dtype("float64"),
                                              jnp.bfloat16.dtype):
        from repro.kernels.quant_blockwise.ops import quantize
        q, s = quantize(jnp.asarray(a))
        q = np.asarray(q)
        payload = q.tobytes()
        meta.update(quant=True, block=int(q.shape[1]),
                    scales=_compress(np.asarray(s).tobytes(), codec))
    else:
        payload = np.ascontiguousarray(np.asarray(a)).tobytes()
        meta.update(quant=False)
    (keys,), _ = array_chunk_digests_many([payload], CHUNK)
    chunks, clens = {}, []
    for d, raw in zip(keys, split_chunks(payload, CHUNK)):
        chunks.setdefault(d, encode_chunk(raw, codec))
        clens.append(len(chunks[d]) - 1)
    return dict(meta, chunks=keys, clens=clens), chunks


def _capture(a, codec):
    red = StateReducer(codec=codec, chunk_bytes=CHUNK)
    return red, red.serialize_names(ExecutionState({"x": a}), {"x"})


def _bits(a) -> tuple:
    a = np.asarray(a)
    return a.shape, str(a.dtype), np.ascontiguousarray(a).tobytes()


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("kind", ARRAYS)
def test_chunks_and_manifest_equal_the_tobytes_path(kind, codec):
    a = ARRAYS[kind]
    _, ser = _capture(a, codec)
    meta, chunks = _old_capture(a, codec)
    assert ser.blobs["x"].arrays == [meta]
    assert ser.chunks == chunks
    assert all(type(c) is bytes for c in ser.chunks.values())


@pytest.mark.parametrize("source", ["capture", "store"])
@pytest.mark.parametrize("codec", LOSSLESS)
@pytest.mark.parametrize("kind", ARRAYS)
def test_restored_array_is_bit_equal_writable_and_its_own(kind, codec,
                                                          source):
    a = ARRAYS[kind]
    red, ser = _capture(a, codec)
    held = list(ser.chunks.values())
    store = None
    if source == "store":
        store = MemoryChunkStore()
        store.put_many(ser.chunks)
        ser.chunks = {}
    out = red.deserialize(ser, chunk_store=store)["x"]
    assert _bits(out) == _bits(a)
    if isinstance(a, jax.Array):
        # a device array comes back on the default device
        assert isinstance(out, jax.Array)
        assert out.devices() == {jax.devices()[0]}
        return
    assert isinstance(out, np.ndarray)
    assert out.flags.writeable and out.flags.owndata
    assert not any(np.shares_memory(out, np.frombuffer(c, np.uint8))
                   for c in held)
    if isinstance(a, np.ndarray):
        assert not np.shares_memory(out, a)


def test_changing_the_host_array_after_capture_leaves_the_chunks_as_captured():
    a = np.arange(20000, dtype=np.float32)
    before = a.copy()
    red, ser = _capture(a, "none")
    stored = dict(ser.chunks)
    a[:] = -1.0
    _, chunks = _old_capture(before, "none")
    assert ser.chunks == stored == chunks
    np.testing.assert_array_equal(red.deserialize(ser)["x"], before)


def test_an_array_of_repeated_chunks_restores():
    a = np.zeros((7, CHUNK + 100), np.uint8)     # 7 rows, chunks repeat
    red, ser = _capture(a, "none")
    keys = ser.blobs["x"].arrays[0]["chunks"]
    assert len(set(keys)) < len(keys)
    out = red.deserialize(ser)["x"]
    assert _bits(out) == _bits(a)
    out[0, 0] = 1                                # writable, and its own
    assert a[0, 0] == 0


@pytest.mark.parametrize("with_store", [False, True])
def test_a_missing_chunk_is_a_key_error(with_store):
    red, ser = _capture(np.arange(5000, dtype=np.int32), "none")
    gone = ser.blobs["x"].arrays[0]["chunks"][1]
    store = None
    if with_store:
        store = MemoryChunkStore()
        store.put_many({d: c for d, c in ser.chunks.items() if d != gone})
        ser.chunks = {}
    else:
        del ser.chunks[gone]
    with pytest.raises(KeyError):
        red.deserialize(ser, chunk_store=store)


def test_chunks_that_overrun_the_array_are_refused():
    red, ser = _capture(np.arange(5000, dtype=np.int32), "none")
    meta = ser.blobs["x"].arrays[0]
    meta["shape"] = (4000,)
    with pytest.raises(ValueError):
        red.deserialize(ser)


@pytest.mark.parametrize("kind,copies", [
    ("contiguous", 2),       # the encode copy and the restore fill
    ("fortran", 3),          # and the copy into C order first
    ("repeated", None),      # encode copies each distinct chunk once
])
def test_copied_bytes_counts_each_whole_payload_copy(kind, copies):
    a = {"contiguous": np.arange(30000, dtype=np.float32),
         "fortran": np.asfortranarray(
             np.arange(30000, dtype=np.float32).reshape(100, 300)),
         "repeated": np.zeros(10 * CHUNK, np.uint8)}[kind]
    red = StateReducer(codec="none", chunk_bytes=CHUNK)
    with spans.recording():
        ser = red.serialize_names(ExecutionState({"x": a}), {"x"})
        red.deserialize(ser)
    copied = spans.window()["counters"]["copied_bytes"]
    if copies is None:
        assert copied == CHUNK + a.nbytes
    else:
        assert copied == copies * a.nbytes

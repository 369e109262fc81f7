"""Notebook state reducer (paper §II-D): reduced capture, chunked
serialization onto a content-addressed store, content hashing, delta
migration, compression codecs.

Pipeline (faithful to the paper, TPU-adapted per DESIGN.md §4, then
generalized from name to chunk granularity):

1. ``reduce``: AST Load-closure over the live namespace -> needed names only.
2. ``serialize``: arrays leave the pickle stream and their raw buffers are
   split into fixed-size chunks, each compressed and content-addressed by a
   64-bit digest (optionally block-quantized to int8 on device first);
   everything else pickles.  Identical chunks dedup within one capture.
   Serialization failure => the caller executes locally (§II-D).
3. ``digests``: content hash per name — jax arrays hash *on device* with the
   Pallas ``hash_delta`` kernel (per-block digest lanes, not tensors, cross
   to host; folded to one 64-bit digest per leaf); host objects hash with
   blake2b over their serialized bytes.  Array chunk digests reuse the same
   per-block vector.
4. ``delta``: per-name digests pick which names move; per-chunk manifests
   then ship only the chunks the receiver's store does not already hold, so
   a 1-element update to a 1 GB array moves one chunk, not the array.
   Deletions are propagated as tombstones.
5. codecs: none | zlib (paper's choice) | zstd | quant8+zstd (lossy,
   opt-in), applied chunk-by-chunk and recorded per chunk.
"""
from __future__ import annotations

import contextvars
import hashlib
import io
import marshal
import pickle
import types
import zlib
from dataclasses import dataclass, field
from typing import Any

import numpy as np

try:
    import zstandard as _zstd
except ImportError:  # pragma: no cover
    _zstd = None

import jax
import jax.numpy as jnp

from repro import spans
from repro.core.astdeps import cell_dependencies
from repro.core.chunkstore import (
    CHUNK_BYTES, array_chunk_digests_many, chunk_body, encode_chunk,
    split_chunks,
)
from repro.core.state import ExecutionState

CODECS = ("none", "zlib", "zstd", "quant8+zstd")

DIGEST_BYTES = 8     # manifest cost of advertising one chunk digest


class SerializationFailure(Exception):
    """Paper §II-D: on serialization failure the cell executes locally."""


# ----------------------------------------------------------------------
# codec helpers (scales + pickle streams; chunks carry their own codec tag)
# ----------------------------------------------------------------------

def _compress(data: bytes, codec: str) -> bytes:
    if codec == "none":
        return data
    if codec == "zlib":
        return zlib.compress(data, level=6)
    if codec in ("zstd", "quant8+zstd"):
        if _zstd is None:
            return zlib.compress(data, level=6)
        return _zstd.ZstdCompressor(level=6).compress(data)
    raise ValueError(codec)


def _decompress(data: bytes, codec: str) -> bytes:
    if codec == "none":
        return data
    if codec == "zlib":
        return zlib.decompress(data)
    if codec in ("zstd", "quant8+zstd"):
        if _zstd is None:
            return zlib.decompress(data)
        return _zstd.ZstdDecompressor().decompress(data)
    raise ValueError(codec)


# ----------------------------------------------------------------------
# array-aware pickling
# ----------------------------------------------------------------------

def _is_array(x) -> bool:
    return isinstance(x, (np.ndarray, jax.Array)) and not np.isscalar(x)


# Target namespace for function-globals rebinding during deserialization:
# a migrated cell-defined function must resolve its globals in the
# *destination* environment's namespace (paper: the remote kernel).
_TARGET_NS: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "repro_target_ns", default=None)


def _make_function(code_bytes: bytes, name: str, defaults, closure_vals):
    code = marshal.loads(code_bytes)  # noqa: S302 — our own serialized stream
    g = _TARGET_NS.get()
    if g is None:
        g = {"__builtins__": __builtins__}
    closure = tuple(types.CellType(v) for v in closure_vals) or None
    fn = types.FunctionType(code, g, name, defaults, closure)
    return fn


def _by_value(fn: types.FunctionType) -> bool:
    """Cell/exec-defined functions can't be pickled by reference."""
    import sys
    mod = getattr(fn, "__module__", None)
    if mod in (None, "__main__"):
        return True
    m = sys.modules.get(mod)
    return m is None or getattr(m, fn.__qualname__.split(".")[0], None) is not fn


class _Pickler(pickle.Pickler):
    def __init__(self, f, store: list):
        super().__init__(f, protocol=pickle.HIGHEST_PROTOCOL)
        self._store = store

    def persistent_id(self, obj):
        # arrays leave the stream as they are: pulling a device array to
        # the host happens after pickling, outside its failure handler
        if _is_array(obj):
            self._store.append(obj)
            return ("arr", len(self._store) - 1)
        return None

    def reducer_override(self, obj):
        if isinstance(obj, types.FunctionType) and _by_value(obj):
            closure_vals = tuple(c.cell_contents for c in (obj.__closure__ or ()))
            return (_make_function, (marshal.dumps(obj.__code__), obj.__name__,
                                     obj.__defaults__, closure_vals))
        return NotImplemented


class _Unpickler(pickle.Unpickler):
    def __init__(self, f, store: list):
        super().__init__(f)
        self._store = store

    def persistent_load(self, pid):
        kind, idx = pid
        assert kind == "arr"
        return self._store[idx]


def host_array(a) -> np.ndarray:
    """``a`` as a host numpy array, without pinning it to ``a``.

    ``np.asarray`` of an accelerator-resident array caches the host copy
    on that array for as long as the array lives, which would keep a
    second host copy of every captured leaf.  So the pull goes through a
    transient device copy that carries the cache and dies with the result
    (on the CPU backend ``np.asarray`` aliases and caches nothing).
    ``pull_bytes`` counts the array's bytes on every backend."""
    if not isinstance(a, jax.Array):
        return np.asarray(a)
    spans.count("pull_bytes", a.nbytes)
    with spans.span("reducer.pull"):
        if any(d.platform != "cpu" for d in a.devices()):
            a = jax.device_put(a, a.sharding, may_alias=False)
        return np.asarray(a)


def _byte_view(a: np.ndarray):
    """The bytes of ``a`` in C order as a flat ``uint8`` view, copied only
    where ``a`` is not C-contiguous; ``tobytes()`` where its dtype cannot
    be viewed as bytes (object arrays).  Each copy counts in
    ``copied_bytes``."""
    c = np.ascontiguousarray(a)
    try:
        view = c.reshape(-1).view(np.uint8)
    except (TypeError, ValueError):
        spans.count("copied_bytes", c.nbytes)
        return c.tobytes()
    if not a.flags.c_contiguous:
        spans.count("copied_bytes", c.nbytes)
    return view


def _prepare_array(a, codec: str) -> tuple[dict, Any]:
    """Array (host or device) -> (chunk-manifest meta sans digests, raw
    payload).  A device array is quantized where it lives, then pulled to
    the host.  The payload is a byte view of the host array where one
    exists (:func:`_byte_view`): a pulled device array is private to the
    capture, and a host array is the namespace's own, read in place.

    A ``jax.Array`` on one device is marked ``device`` in the meta, so
    that restore puts it back on a device; a host array's meta has no such
    key, and a sharded array is restored to the host whole.

    Digesting is deferred so the caller can batch every payload of a
    capture into one device launch (:func:`array_chunk_digests_many`)."""
    meta = {"shape": a.shape, "dtype": str(a.dtype)}
    if isinstance(a, jax.Array) and len(a.devices()) == 1:
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
        a = host_array(a)
        with spans.span("reducer.payload"):
            payload = _byte_view(a)
        meta.update(quant=False)
    return meta, payload


@spans.spanned("reducer.assemble")
def _decode_array(meta: dict, codec: str, chunks: dict[int, bytes],
                  store=None):
    """One array of a manifest, from its chunks.  An array captured from a
    device comes back as a ``jax.Array`` on the default device
    (``jax.devices()[0]``), where this process can hold its dtype; every
    other array comes back as a new, writable numpy array."""
    shape = tuple(meta["shape"])
    dtype = np.dtype(meta["dtype"]) if meta["dtype"] != "bfloat16" else jnp.bfloat16.dtype
    # a dtype this process's jax would narrow (64-bit without x64) stays
    # on the host, bit for bit
    on_device = (meta.get("device", False)
                 and jax.dtypes.canonicalize_dtype(dtype) == dtype)

    def fetch(d: int):
        if d in chunks:
            return chunk_body(chunks[d])
        if store is not None and store.has(d):
            return chunk_body(store.get(d))
        raise KeyError(f"missing chunk {d:016x}")

    if meta["quant"]:
        from repro.kernels.quant_blockwise.ops import dequantize
        raw = b"".join(fetch(d) for d in meta["chunks"])
        block = int(meta["block"])   # quant block size travels in the meta
        q = np.frombuffer(raw, np.int8).reshape(-1, block)
        s = np.frombuffer(_decompress(meta["scales"], codec), np.float32)
        x = dequantize(jnp.asarray(q), jnp.asarray(s), shape,
                       jnp.dtype(dtype))
        return x if on_device else np.asarray(x)
    # one copy: each chunk body lands at its offset in the new array (a
    # body that runs past the end fails the slice assignment)
    out = np.empty(shape, dtype)
    dst = memoryview(out.reshape(-1).view(np.uint8))
    pos = 0
    for d in meta["chunks"]:
        body = fetch(d)
        dst[pos:pos + len(body)] = body
        pos += len(body)
    if pos != len(dst):
        raise ValueError(f"chunks of a {shape} {dtype} array hold {pos} of "
                         f"its {len(dst)} bytes")
    spans.count("copied_bytes", pos)
    if not on_device:
        return out
    # the host buffer is private to this restore: nothing writes it while
    # the transfer runs, and it dies with the last reference to it
    with spans.span("reducer.place"):
        return jax.device_put(out, jax.devices()[0])


# ----------------------------------------------------------------------
# public containers
# ----------------------------------------------------------------------

@dataclass
class SerializedName:
    pickle_bytes: bytes
    arrays: list[dict]

    @property
    def nbytes(self) -> int:
        """Standalone transfer cost of this name (chunks shared with other
        names in the same capture are counted here per reference)."""
        n = len(self.pickle_bytes)
        for a in self.arrays:
            n += sum(a["clens"]) + len(a.get("scales", b""))
        return n

    def chunk_digests(self) -> list[int]:
        return [d for a in self.arrays for d in a["chunks"]]


@dataclass
class SerializedState:
    codec: str
    blobs: dict[str, SerializedName]
    chunks: dict[int, bytes] = field(default_factory=dict)  # digest -> encoded
    deleted: tuple[str, ...] = ()
    modules: tuple[str, ...] = ()
    digests: dict[str, int] = field(default_factory=dict)
    skipped: tuple[str, ...] = ()

    @property
    def nbytes(self) -> int:
        """Full transfer cost: pickle streams + scales + every unique chunk
        (what crosses the wire to a receiver holding nothing)."""
        n = sum(len(b.pickle_bytes)
                + sum(len(a.get("scales", b"")) for a in b.arrays)
                for b in self.blobs.values())
        return n + sum(len(c) - 1 for c in self.chunks.values())

    @property
    def ref_nbytes(self) -> int:
        """Whole-name accounting (the pre-CAS protocol): every chunk counted
        once per reference, no cross-name dedup — the paper's Table-II
        measurement of a plain serialized transfer."""
        return sum(b.nbytes for b in self.blobs.values())

    def wire_nbytes(self, held: set[int]) -> int:
        """Transfer cost against a receiver advertising ``held`` chunk
        digests: full streams for pickles/scales, encoded bytes for missing
        chunks, and DIGEST_BYTES per referenced chunk (the manifest)."""
        n = sum(len(b.pickle_bytes)
                + sum(len(a.get("scales", b"")) for a in b.arrays)
                for b in self.blobs.values())
        refs = 0
        counted: set[int] = set()
        for b in self.blobs.values():
            for d in b.chunk_digests():
                refs += 1
                if d in held or d in counted or d not in self.chunks:
                    continue
                counted.add(d)
                n += len(self.chunks[d]) - 1
        return n + refs * DIGEST_BYTES

    def missing_chunks(self, held: set[int]) -> dict[int, bytes]:
        return {d: c for d, c in self.chunks.items() if d not in held}


# ----------------------------------------------------------------------
# the reducer
# ----------------------------------------------------------------------

class StateReducer:
    def __init__(self, codec: str = "zlib", reduce_state: bool = True,
                 chunk_bytes: int = CHUNK_BYTES):
        assert codec in CODECS, codec
        self.codec = codec
        self.reduce_state = reduce_state
        # chunk_bytes <= 0 => one chunk per payload (whole-name granularity,
        # the pre-CAS baseline; benchmarks compare against it)
        self.chunk_bytes = int(chunk_bytes)
        # (name, array-slot) -> (block_h64, chunk_digests, payload_len):
        # priors for the fused digest+compare launch, so re-serializing a
        # partially-changed array folds only its changed chunks on host.
        # Reuse is content-verified on device, so a stale entry can only
        # cost a recompute, never a wrong digest.
        self._chunk_cache: dict[tuple[str, int], tuple] = {}

    # -- step 1: which names does this cell need? ----------------------
    def reduce(self, state: ExecutionState, cell_source: str):
        if not self.reduce_state:
            names = set(state.names())
            return names, set(), None
        needed, modules, info = cell_dependencies(cell_source, state.ns)
        return needed, modules, info

    # -- step 2/3: serialize + digest -----------------------------------
    @spans.spanned("reducer.capture")
    def serialize_names(self, state: ExecutionState, names,
                        codec: str | None = None,
                        on_error: str = "raise",
                        digests: dict[str, int] | None = None
                        ) -> SerializedState:
        """on_error="raise": SerializationFailure aborts (caller runs the cell
        locally, §II-D).  on_error="skip": unserializable names simply don't
        travel (used on return migrations — the object stays remote).

        ``digests`` lets a caller that already holds this capture's content
        digests (``delta_names`` returns them) pass them through instead of
        re-digesting.

        Chunk digesting is two-pass: pass 1 pickles every name and collects
        raw array payloads; pass 2 digests *all* payloads in one device
        launch + one host sync (with on-device compare against the previous
        capture's block lanes, so unchanged chunks skip their host fold);
        pass 3 encodes chunks with the original per-name rollback."""
        codec = codec or self.codec
        blobs: dict[str, SerializedName] = {}
        chunks: dict[int, bytes] = {}
        skipped: list[str] = []
        prepared: list[tuple[str, bytes, list]] = []
        for name in sorted(names):
            obj = state.ns[name]
            with spans.span("reducer.pickle"):
                try:
                    store: list = []
                    buf = io.BytesIO()
                    _Pickler(buf, store).dump(obj)
                except Exception as e:  # noqa: BLE001 — paper: fall back
                    if on_error == "skip":
                        skipped.append(name)
                        continue
                    raise SerializationFailure(f"{name}: {e}") from e
                stream = _compress(buf.getvalue(), codec)
            # device work (quantize, device->host pull) runs outside the
            # handler: a device error is not the paper's pickling failure
            # and must not quietly turn into a local run
            arrays = [_prepare_array(a, codec) for a in store]
            prepared.append((name, stream, arrays))

        keys = [(name, k) for name, _, arrs in prepared
                for k in range(len(arrs))]
        payloads = [p for _, _, arrs in prepared for _, p in arrs]
        digest_lists, h64s = array_chunk_digests_many(
            payloads, self.chunk_bytes,
            priors=[self._chunk_cache.get(k) for k in keys])
        if len(self._chunk_cache) > 4096:   # bounded: priors are a cache
            self._chunk_cache.clear()
        for key, n, digs, h64 in zip(keys, map(len, payloads), digest_lists,
                                     h64s):
            self._chunk_cache[key] = (h64, digs, n)
        del payloads       # each payload is freed once its chunks exist

        with spans.span("reducer.encode"):
            pos = 0
            for name, pickle_bytes, arrays in prepared:
                digs_here = digest_lists[pos:pos + len(arrays)]
                pos += len(arrays)
                # chunks newly inserted by this name; an earlier name's chunks
                # were inserted under *its* entry, so rolling these back on a
                # skip can never orphan a previous blob's references
                added: list[int] = []
                try:
                    metas = []
                    for k, digests_a in enumerate(digs_here):
                        meta, payload = arrays[k]
                        arrays[k] = None     # the chunks below now hold it
                        # the one copy of a payload: views of it, each
                        # encoded into new bytes.  A host leaf's payload is
                        # the namespace's live array, so the bytes stored
                        # are those its chunk keys were computed from:
                        # capture runs on the caller's thread, and nothing
                        # runs the notebook between passes 1 and 3
                        clens = []
                        encoded = 0
                        for d, chunk in zip(digests_a,
                                            split_chunks(memoryview(payload),
                                                         self.chunk_bytes)):
                            if d not in chunks:
                                chunks[d] = encode_chunk(chunk, codec)
                                added.append(d)
                                encoded += len(chunk)
                            # the 1-byte codec tag is store framing, not wire
                            # payload
                            clens.append(len(chunks[d]) - 1)
                        spans.count("copied_bytes", encoded)
                        metas.append(dict(meta, chunks=digests_a, clens=clens))
                    blobs[name] = SerializedName(pickle_bytes=pickle_bytes,
                                                 arrays=metas)
                except Exception as e:  # noqa: BLE001 — paper: fall back
                    for d in added:
                        chunks.pop(d, None)
                    if on_error == "skip":
                        skipped.append(name)
                        continue
                    raise SerializationFailure(f"{name}: {e}") from e
        ser = SerializedState(codec=codec, blobs=blobs, chunks=chunks)
        if digests is None:
            ser.digests = self.digest_many({n: state.ns[n] for n in blobs})
        else:
            ser.digests = {n: digests[n] for n in blobs if n in digests}
            missing = [n for n in blobs if n not in digests]
            if missing:
                ser.digests.update(self.digest_many(
                    {n: state.ns[n] for n in missing}))
        ser.skipped = tuple(skipped)
        return ser

    @spans.spanned("reducer.restore")
    def deserialize(self, ser: SerializedState,
                    target_ns: dict | None = None,
                    chunk_store=None) -> dict[str, Any]:
        """Rebuild objects; chunks resolve from ``ser.chunks`` first, then
        from ``chunk_store`` (the receiver's CAS).

        Counts ``placed_bytes``: the bytes of the arrays restored onto a
        device (:func:`_decode_array`)."""
        token = _TARGET_NS.set(target_ns)
        placed = 0
        try:
            out: dict[str, Any] = {}
            for name, blob in ser.blobs.items():
                store = [_decode_array(m, ser.codec, ser.chunks, chunk_store)
                         for m in blob.arrays]
                placed += sum(a.nbytes for a in store
                              if isinstance(a, jax.Array))
                buf = io.BytesIO(_decompress(blob.pickle_bytes, ser.codec))
                out[name] = _Unpickler(buf, store).load()
            spans.count("placed_bytes", placed)
            return out
        finally:
            _TARGET_NS.reset(token)

    # -- step 3: content digests ---------------------------------------
    @staticmethod
    def _hashable_leaf(a):
        """Map a leaf to a form whose uint32 hashing keeps *every* bit.

        With x64 disabled ``jnp.asarray`` silently narrows int64/float64,
        and the device prep keeps only the (real-part, low-bit) lanes of a
        complex array — a change confined to the dropped bits would hash
        identically and the delta would drop a real update.  So any dtype
        wider than 4 bytes (and any complex dtype, host or device) is
        re-laned to a contiguous uint32 view on the host.  The re-lane
        never falls through silently: a buffer that cannot be viewed as
        uint32 lanes is hashed via its zero-padded raw bytes, and an
        array with no stable bit pattern (object dtype) raises."""
        wide = a.dtype.itemsize > 4 or a.dtype.kind == "c"
        if isinstance(a, jax.Array) and not wide:
            return a                      # device leaf: hash on device
        a = host_array(a)
        if a.dtype.kind == "O":
            raise TypeError("object arrays have no stable bit pattern")
        if not wide and a.dtype.kind in "biuf":
            return a
        a = np.ascontiguousarray(a)
        try:
            return a.reshape(-1).view(np.uint32)
        except (TypeError, ValueError):
            buf = a.tobytes()
            buf += b"\0" * ((-len(buf)) % 4)
            return np.frombuffer(buf, np.uint32)

    def _array_digest(self, a) -> int:
        """Per-leaf device digest (wide host dtypes re-lane'd first)."""
        from repro.kernels.hash_delta.ops import tensor_digest
        return tensor_digest(self._hashable_leaf(a))

    def _host_digest(self, obj) -> int:
        """Pickle-stream blake2b for objects that are not pure array trees."""
        return self._host_digest_sized(obj)[0]

    @staticmethod
    def _host_digest_sized(obj) -> tuple[int, int]:
        """(:meth:`_host_digest`, bytes it read: the pickle stream and the
        arrays taken out of it)."""
        try:
            store: list = []
            buf = io.BytesIO()
            _Pickler(buf, store).dump(obj)
        except Exception:
            return -1, 0  # unhashable => always migrate (paper §II-D)
        h = hashlib.blake2b(buf.getvalue(), digest_size=8)
        for a in store:
            h.update(np.ascontiguousarray(host_array(a)).tobytes())
            h.update(str(a.shape).encode())
        nbytes = buf.getbuffer().nbytes + sum(a.nbytes for a in store)
        return int.from_bytes(h.digest(), "little"), nbytes

    def digest(self, obj) -> int:
        if _is_array(obj):
            return self._array_digest(obj)
        leaves, treedef = jax.tree_util.tree_flatten(obj)
        if leaves and all(_is_array(l) for l in leaves):
            h = hashlib.blake2b(str(treedef).encode(), digest_size=8)
            for l in leaves:
                h.update(self._array_digest(l).to_bytes(8, "little"))
            return int.from_bytes(h.digest(), "little")
        return self._host_digest(obj)

    def _split_for_batch(self, objs: dict[str, Any]):
        """Partition names into the batched-digest plan.

        Returns (slots, leaves, host) where ``leaves`` is the flat leaf
        list for one batched launch and each slot is (name, treedef|None,
        leaf_count) consuming that many leaves in order; ``host`` holds the
        names digested via the pickle path."""
        slots: list[tuple[str, Any, int]] = []
        leaves: list = []
        host: dict[str, Any] = {}
        for n, obj in objs.items():
            if _is_array(obj):
                slots.append((n, None, 1))
                leaves.append(self._hashable_leaf(obj))
                continue
            ls, treedef = jax.tree_util.tree_flatten(obj)
            if ls and all(_is_array(l) for l in ls):
                slots.append((n, treedef, len(ls)))
                leaves.extend(self._hashable_leaf(l) for l in ls)
            else:
                host[n] = obj
        return slots, leaves, host

    @staticmethod
    def _fold_slots(slots, leaf_digests) -> dict[str, int]:
        out: dict[str, int] = {}
        i = 0
        for n, treedef, k in slots:
            if treedef is None:
                out[n] = leaf_digests[i]
            else:
                h = hashlib.blake2b(str(treedef).encode(), digest_size=8)
                for d in leaf_digests[i:i + k]:
                    h.update(d.to_bytes(8, "little"))
                out[n] = int.from_bytes(h.digest(), "little")
            i += k
        return out

    def digest_many(self, objs: dict[str, Any]) -> dict[str, int]:
        """Digest a whole manifest: every array leaf across every name is
        packed into ONE kernel launch with ONE host sync (vs one launch +
        one ``np.asarray`` round-trip per leaf), bit-identical to calling
        :meth:`digest` per name."""
        from repro.kernels.hash_delta.ops import digest_leaves
        slots, leaves, host = self._split_for_batch(objs)
        out = {n: self._host_digest(o) for n, o in host.items()}
        if slots:
            ds = digest_leaves(leaves)
            out.update(self._fold_slots(slots, ds))
        return out

    def digests(self, state: ExecutionState, names) -> dict[str, int]:
        return self.digest_many({n: state.ns[n] for n in names
                                 if n in state.ns})

    # -- step 4: delta ---------------------------------------------------
    @spans.spanned("reducer.delta")
    def delta_names(self, state: ExecutionState, names,
                    known: dict[str, int]):
        """Returns (names to send, tombstones, sender digests).
        ``known`` = receiver's current content view.

        Pure-array names ride the fused digest->compare->gather path: the
        fresh digests are compared against ``known`` on device and only the
        changed-name index list crosses to the host — one launch, one sync
        for the whole manifest.

        Counts ``kept_bytes``: the bytes of the names digested here that
        the receiver already knows, so that they are not sent (array leaves
        at their size, other objects at their pickle stream's)."""
        from repro.kernels.hash_delta.ops import digest_leaves_delta
        objs = {n: state.ns[n] for n in names if n in state.ns}
        slots, leaves, host = self._split_for_batch(objs)
        sized = {n: self._host_digest_sized(o) for n, o in host.items()}
        here = {n: d for n, (d, _) in sized.items()}
        size = {n: b for n, (_, b) in sized.items()}
        send = {n for n, d in here.items() if d == -1 or known.get(n) != d}
        if slots:
            # per-leaf priors: a single-array name compares on device
            # against the receiver's view of that name; tree leaves carry
            # no per-leaf prior (their name digest is a host-side blake2b
            # fold) so their real compare happens after the fold
            prior: list = []
            leaf_name: dict[int, str] = {}   # flat leaf idx -> array name
            i = 0
            for n, treedef, k in slots:
                if treedef is None:
                    prior.append(known.get(n))
                    leaf_name[i] = n
                else:
                    prior.extend([None] * k)
                i += k
            ds, changed = digest_leaves_delta(leaves, prior)
            folded = self._fold_slots(slots, ds)
            here.update(folded)
            send.update(leaf_name[j] for j in changed if j in leaf_name)
            send.update(n for n, treedef, _k in slots
                        if treedef is not None and known.get(n) != folded[n])
            size.update((n, sum(a.nbytes for a in
                                jax.tree_util.tree_leaves(objs[n])))
                        for n, _t, _k in slots)
        spans.count("kept_bytes", sum(b for n, b in size.items()
                                      if n not in send))
        dead = {n for n in known if n not in state.ns}
        return send, dead, here

"""Jitted wrappers: on-device content digests for the state plane.

``block_digests`` exposes the per-1024-element block digest vector that the
content-addressed chunk store consumes (each block carries two independent
uint32 lanes = one 64-bit identity).  ``tensor_digest`` folds that vector
into a single **64-bit** leaf digest: both lanes are reduced on device and
combined on the host as ``(hi << 32) | lo`` — Pallas/XLA arithmetic stays
uint32 throughout, so no x64 mode is required, yet the digest space is a
true 2^64 (the pre-CAS version returned a single uint32).

**Which implementation runs.**  :func:`kernel_mode` is the one place that
chooses: the compiled Pallas kernel where the data lives on a TPU, the XLA
formulation (``ref.block_hash_ref``) elsewhere.  Both give the same bits,
so a digest never depends on where it was computed.  ``interpret``
runs the Pallas kernel in interpret mode; only tests set it.

**Batched manifest digesting.**  ``digest_leaves`` packs the leaves of a
manifest — ragged sizes, mixed numpy/jax residency — into block grids of at
most :data:`BATCH_ROWS` rows and digests each grid in one kernel launch
with one device->host sync, instead of one launch + one ``np.asarray``
round-trip per leaf.  A capture below one batch therefore costs exactly one
launch and one sync, and a larger one holds at most one batch beyond its
resident state on the device.  The result is bit-identical to the per-leaf
path: each leaf is padded to its own block boundary (so per-block digests
are unchanged) and the per-leaf fold is an unsigned 32-bit weighted sum,
which is exactly associative/commutative mod 2^32 — ``segment_sum`` over a
grid, and the host's sum of a split leaf's partial lanes across grids,
therefore reproduce ``tensor_digest`` bit for bit.

The hash path's steps are spans and counters of :mod:`repro.spans`:
``hash.stage`` (host copies into the staging buffer), ``hash.upload`` and
``upload_bytes`` (:func:`to_device`), ``hash.wait`` (launch, device time
and the lanes' return), ``widened_bytes`` (the uint32 grid each hash
program reads, counted at its launch) and ``host_syncs`` (device->host
materializations: one per ``tensor_digest``, one per batch).
``HOST_SYNCS`` reads ``host_syncs`` since :func:`reset_host_syncs`, so
benchmarks and tests can assert the O(leaves) -> O(batches) reduction.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.kernels.hash_delta.kernel import (
    block_hash_compare_kernel, block_hash_kernel,
)
from repro.kernels.hash_delta.ref import block_hash_compare_ref, block_hash_ref

BLOCK = 1024
LANES = 2
BATCH_ROWS = 1 << 16            # block rows per launch: 256 MiB of uint32
_FOLD = np.uint32(2246822519)   # per-block position weight (golden-prime)

_sync_base = 0                  # the ``host_syncs`` count at the last reset


def _note_sync(n: int = 1) -> None:
    spans.count("host_syncs", n)


def reset_host_syncs() -> None:
    global _sync_base
    _sync_base = spans.total("host_syncs")


def note_host_sync(n: int = 1) -> None:
    """Record a device->host sync issued by a caller (e.g. the chunk
    store's batched fold pulling the packed digest vector)."""
    _note_sync(n)


def _note_launch(rows: int) -> None:
    """Count the uint32 grid of ``rows`` blocks a hash program reads."""
    spans.count("widened_bytes", rows * BLOCK * 4)


def __getattr__(name: str):
    if name == "HOST_SYNCS":    # device->host syncs since reset_host_syncs()
        return spans.total("host_syncs") - _sync_base
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# host constants (no tracer leak): one odd weight vector per lane.  Lane 0
# keeps the historical 0xD1657 stream; lane 1 is an independent stream.
_W = np.stack([
    np.random.default_rng(0xD1657).integers(1, 2**32, size=BLOCK,
                                            dtype=np.uint32) | 1,
    np.random.default_rng(0xD1658).integers(1, 2**32, size=BLOCK,
                                            dtype=np.uint32) | 1,
])


def kernel_mode(x, interpret: bool = False) -> str:
    """Implementation for hashing ``x``: ``"pallas"`` when it lives on a
    TPU, ``"xla"`` otherwise, ``"interpret"`` when a test asks for the
    Pallas kernel in interpret mode.  Host data counts as living on the
    default device, where :func:`to_device` and ``jit`` place it."""
    if interpret:
        return "interpret"
    if isinstance(x, jax.Array):
        dev = next(iter(x.devices()))
    else:
        dev = jax.devices()[0]
    return "pallas" if dev.platform == "tpu" else "xla"


def _lanes(x2d, mode: str):
    w = jnp.asarray(_W)
    if mode == "xla" or x2d.shape[0] == 0:   # a kernel needs a non-empty grid
        return block_hash_ref(x2d, w)
    return block_hash_kernel(x2d, w, interpret=mode == "interpret")


def _lanes_compare(x2d, prior, has_prior, mode: str):
    w = jnp.asarray(_W)
    if mode == "xla" or x2d.shape[0] == 0:
        return block_hash_compare_ref(x2d, w, prior, has_prior)
    return block_hash_compare_kernel(x2d, w, prior, has_prior,
                                     interpret=mode == "interpret")


def _as_u32_blocks(x):
    if jnp.issubdtype(x.dtype, jnp.floating):
        raw = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    elif x.dtype.itemsize == 4:
        raw = jax.lax.bitcast_convert_type(x, jnp.uint32)
    else:  # narrow/wide ints, bool: value-hash via uint32 cast
        raw = x.astype(jnp.uint32)
    flat = raw.reshape(-1).astype(jnp.uint32)
    pad = (-flat.shape[0]) % BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)


@functools.partial(jax.jit, static_argnames=("mode",))
def _block_lanes(x, *, mode: str):
    return _lanes(_as_u32_blocks(x), mode)


def block_digests(x):
    """Any tensor -> (nb, 2) uint32 per-block digest lanes (on device).

    One row per 1024-element block; the two lanes together are the block's
    64-bit identity.  Only this (nb, 2) vector crosses to the host — never
    the tensor itself."""
    _note_launch(-(-x.size // BLOCK))
    return _block_lanes(x, mode=kernel_mode(x))


@functools.partial(jax.jit, static_argnames=("mode",))
def _digest_lanes(x, *, mode: str):
    """Weighted fold of the per-block vector -> (2,) uint32 (host-free)."""
    h2 = _block_lanes(x, mode=mode)
    idx = (jnp.arange(h2.shape[0], dtype=jnp.uint32)
           * _FOLD + jnp.uint32(1))
    return jnp.sum(h2 * idx[:, None], axis=0, dtype=jnp.uint32)


def tensor_digest(x, *, interpret: bool = False) -> int:
    """Any tensor -> one 64-bit int digest (content hash for delta migration)."""
    _note_launch(-(-int(np.size(x)) // BLOCK))
    with spans.span("hash.wait"):
        lo, hi = np.asarray(_digest_lanes(x, mode=kernel_mode(x, interpret)))
    _note_sync()
    return (int(hi) << 32) | int(lo)


@functools.partial(jax.jit, static_argnames=("mode",))
def _packed_lanes(u8, *, mode: str):
    return _lanes(u8.astype(jnp.uint32).reshape(-1, BLOCK), mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def _packed_lanes_compare(u8, prior, has_prior, *, mode: str):
    return _lanes_compare(u8.astype(jnp.uint32).reshape(-1, BLOCK), prior,
                          has_prior, mode)


def packed_block_digests(u8):
    """Pre-packed byte buffer (size % BLOCK == 0) -> (nb, 2) lanes.

    The caller has already zero-padded each constituent payload to its own
    block boundary, so every row equals the row :func:`block_digests` would
    produce for that payload standalone."""
    _note_launch(u8.size // BLOCK)
    return _packed_lanes(u8, mode=kernel_mode(u8))


def packed_block_digests_compare(u8, prior, has_prior):
    """Fused variant of :func:`packed_block_digests`: digest + compare
    against a prior (nb, 2) lane vector in one launch; returns
    ``(h, changed)`` with ``changed`` (nb, 1) uint32."""
    _note_launch(u8.size // BLOCK)
    return _packed_lanes_compare(u8, prior, has_prior, mode=kernel_mode(u8))


# ----------------------------------------------------------------------
# batched manifest digesting: many ragged leaves, few launches, few syncs
# ----------------------------------------------------------------------

_ALIGN = 64   # XLA:CPU buffer alignment — required for zero-copy import


def aligned_empty(n: int, dtype=np.uint32) -> np.ndarray:
    """1-D ``np.empty(n, dtype)`` on a 64-byte boundary.

    numpy only guarantees 16-byte alignment, which forces jax's dlpack
    import to copy; carving the view out of an oversized uint8 buffer
    makes :func:`to_device` a true zero-copy alias on the CPU."""
    itemsize = np.dtype(dtype).itemsize
    raw = np.empty(n * itemsize + _ALIGN, np.uint8)
    off = (-raw.ctypes.data) % _ALIGN
    return raw[off:off + n * itemsize].view(dtype)


def to_device(a: np.ndarray):
    """Host array -> array on the default device (``jax.devices()[0]``).

    Where that device is the CPU, the result aliases ``a`` (zero-copy when
    64-byte aligned), so callers must not mutate ``a`` while it is in use.
    Elsewhere the buffer is copied to the device."""
    spans.count("upload_bytes", a.nbytes)
    with spans.span("hash.upload"):
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            try:
                return jnp.from_dlpack(a)
            except (TypeError, ValueError, RuntimeError):
                pass     # not shareable: fall through to a copying transfer
        return jax.device_put(a, dev)


_STAGING = threading.local()


def staging_buffer(n: int, dtype=np.uint32) -> np.ndarray:
    """Aligned staging buffer, reused across calls (per thread).

    First-touch page faults dominate the cost of a fresh ``np.empty`` —
    roughly 7x the price of refilling warm pages — so batch digesting
    stages through a recycled buffer.  Reuse is only safe because every
    batch syncs (``device_get``) before the next one fills the buffer:
    once a batch is over, no live device array aliases it.  Requests are
    at most one batch (``BATCH_ROWS * BLOCK`` elements), so that bounds
    what the pool keeps alive per dtype."""
    pool = getattr(_STAGING, "pool", None)
    if pool is None:
        pool = _STAGING.pool = {}
    key = np.dtype(dtype).str
    buf = pool.get(key)
    if buf is None or buf.size < n:
        grown = 0 if buf is None else 2 * buf.size
        buf = pool[key] = aligned_empty(
            max(n, min(grown, BATCH_ROWS * BLOCK)), dtype)
    return buf[:n]


def plan_batches(nbs) -> list[list[tuple[int, int, int]]]:
    """Cut ragged items of ``nbs[i]`` block rows into batches of at most
    :data:`BATCH_ROWS` rows, in order.

    Each batch is a list of pieces ``(item, first_row, rows)``; an item
    that crosses a batch boundary is split there, so every batch but the
    last is exactly full.  Empty items get no piece."""
    batches: list[list[tuple[int, int, int]]] = []
    cur: list[tuple[int, int, int]] = []
    used = 0
    for i, nb in enumerate(nbs):
        row = 0
        while row < nb:
            take = min(nb - row, BATCH_ROWS - used)
            cur.append((i, row, take))
            row += take
            used += take
            if used == BATCH_ROWS:
                batches.append(cur)
                cur, used = [], 0
    if cur:
        batches.append(cur)
    return batches


def _np_u32_flat(a: np.ndarray) -> np.ndarray:
    """Host-exact mirror of :func:`_as_u32_blocks`, unpadded and flat.

    Bit-identity notes: float16/float32 -> float32 is exact in both, and
    float64 -> float32 uses the same IEEE round-to-nearest that jax's
    implicit x64 demotion applies; 4-byte dtypes are reinterpreted; narrow
    and 64-bit ints wrap mod 2^32 exactly as XLA's convert does."""
    if a.dtype.kind == "f":
        raw = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    elif a.dtype.itemsize == 4 and a.dtype.kind in "iu":
        raw = np.ascontiguousarray(a).view(np.uint32)
    else:
        raw = a.astype(np.uint32)
    return raw.reshape(-1)


_prep_blocks = jax.jit(_as_u32_blocks)


def _is_host(a) -> bool:
    return isinstance(a, (np.ndarray, np.generic))


def _pack_batch(leaves, pieces, split: dict):
    """One batch of pieces -> its (rows, BLOCK) uint32 grid on the device.

    Every row equals the row the per-leaf path would have hashed: each
    leaf is padded to its own block boundary.  Host pieces are gathered
    into one aligned staging buffer (one copy pass) and placed on the
    default device with :func:`to_device`; device pieces are prepped where
    they live and never visit the host.  A device leaf split across
    batches is widened once and kept in ``split`` while its batches run
    (the slice-before-widen alternative materializes the flattened leaf
    on a TPU anyway)."""
    host_rows = sum(rows for i, _, rows in pieces if _is_host(leaves[i]))
    dst = staging_buffer(host_rows * BLOCK) if host_rows else None
    parts, off, run_lo = [], 0, 0

    def _close_run():
        if off > run_lo:
            # run offsets are BLOCK-row multiples, so slices stay aligned
            parts.append(to_device(dst[run_lo:off].reshape(-1, BLOCK)))

    for i, row0, rows in pieces:
        a = leaves[i]
        if _is_host(a):
            with spans.span("hash.stage"):
                flat = _np_u32_flat(np.ravel(np.asarray(a))[
                    row0 * BLOCK:(row0 + rows) * BLOCK])
                end = off + flat.size
                dst[off:end] = flat
                off += rows * BLOCK
                dst[end:off] = 0
            continue
        _close_run()
        run_lo = off
        if row0 == 0 and rows == -(-a.size // BLOCK):
            parts.append(_prep_blocks(a))
            continue
        if i not in split:
            split.clear()              # the previous split leaf is done
            split[i] = _prep_blocks(a)
        parts.append(jax.lax.dynamic_slice_in_dim(split[i], row0, rows))
    _close_run()
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _fold_weights(pieces):
    """Per-row fold weight + piece segment id (host-side, shapes static).

    A row's weight depends on its position within its *leaf*, so the
    pieces of a split leaf carry their leaf-local row offsets."""
    rows = np.asarray([r for _, _, r in pieces], np.int64)
    row0 = np.asarray([r0 for _, r0, _ in pieces], np.int64)
    seg = np.repeat(np.arange(len(pieces), dtype=np.int32), rows)
    starts = np.repeat(np.cumsum(rows) - rows - row0, rows)
    local = (np.arange(int(rows.sum()), dtype=np.int64) - starts)
    return local.astype(np.uint32) * _FOLD + np.uint32(1), seg


@functools.partial(jax.jit, static_argnames=("num_segments", "mode"))
def _batched_lanes(x2d, idx, seg, *, num_segments: int, mode: str):
    """One launch over a packed grid -> (num_segments, 2) lane sums.

    The per-leaf fold is a weighted uint32 sum; ``segment_sum`` reorders
    additions but unsigned add is associative/commutative mod 2^32, so the
    result is bit-identical to the per-leaf ``jnp.sum``."""
    h2 = _lanes(x2d, mode)
    return jax.ops.segment_sum(h2 * idx[:, None], seg,
                               num_segments=num_segments)


def _fold_digests(lanes: np.ndarray) -> list[int]:
    lanes = np.asarray(lanes, np.uint64)
    return ((lanes[:, 1] << np.uint64(32)) | lanes[:, 0]).tolist()


def digest_leaves(leaves, *, interpret: bool = False) -> list[int]:
    """Digest a whole manifest of leaves, one launch + one host sync per
    batch of :data:`BATCH_ROWS` block rows.

    Returns one 64-bit digest per leaf, in order, bit-identical to calling
    :func:`tensor_digest` on each leaf individually (a leaf with no blocks
    digests to 0)."""
    leaves = list(leaves)
    totals = np.zeros((len(leaves), LANES), np.uint32)
    split: dict = {}
    for pieces in plan_batches([-(-int(np.size(a)) // BLOCK)
                                for a in leaves]):
        x2d = _pack_batch(leaves, pieces, split)
        with spans.span("hash.stage"):
            idx, seg = _fold_weights(pieces)
        # bucket the segment count so batches share compilations
        nseg = max(8, 1 << (len(pieces) - 1).bit_length())
        _note_launch(x2d.shape[0])
        with spans.span("hash.wait"):
            lanes = np.asarray(_batched_lanes(
                x2d, jnp.asarray(idx), jnp.asarray(seg), num_segments=nseg,
                mode=kernel_mode(x2d, interpret)))
        _note_sync()
        # a leaf has at most one piece per batch; uint32 adds wrap mod 2^32
        totals[[i for i, _, _ in pieces]] += lanes[:len(pieces)]
    return _fold_digests(totals)


def digest_leaves_delta(leaves, prior_digests):
    """Digest + delta for a whole manifest (syncs as :func:`digest_leaves`).

    ``prior_digests`` aligns with ``leaves``: the prior 64-bit digest of
    each leaf, or ``None`` when there is no prior (leaf counts as changed).
    Returns ``(digests, changed)`` — per-leaf 64-bit digests (bit-identical
    to :func:`tensor_digest`) and the sorted index list of leaves whose
    digest differs from its prior.  Only (n, 2) lanes per batch cross to
    the host, and the compare of n digests runs there."""
    digests = digest_leaves(leaves)
    changed = [j for j, (d, p) in enumerate(zip(digests, prior_digests))
               if p is None or p != d]
    return digests, changed

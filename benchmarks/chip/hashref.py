"""The hash formulation that the chip's digests must equal, in NumPy.

Written from the algorithm's description and run on the host, apart from
the program's kernels and from JAX:

- a leaf is read as uint32 lanes: floating point as the bits of its
  float32 value, 4-byte integers as their bits, any other dtype by value;
- the lanes are zero-padded to blocks of ``BLOCK`` elements;
- each element is premixed, ``x ^= x >> 16; x *= PRIME`` (mod 2**32);
- each block gives two lanes, the weighted sums of its premixed elements
  with two fixed odd weight vectors (mod 2**32), each finished with
  ``h ^= h >> 15; h *= PRIME``;
- a leaf's digest is, per lane, the sum over blocks of the block's lane
  times ``block_index * FOLD + 1`` (mod 2**32), and lane 1 is the high
  word of the 64-bit result;
- a chunk of raw bytes is read one byte per uint32 element; its key is
  the 8-byte blake2b of its blocks' 64-bit words (lane 1 high, little
  endian) followed by the chunk's length as 8 little-endian bytes.

A payload longer than the chunk size is cut into chunks of the chunk size,
the last one short.
"""
from __future__ import annotations

import hashlib

import numpy as np

BLOCK = 1024
BATCH_ROWS = 1 << 16
PRIME = np.uint32(2654435761)
FOLD = np.uint32(2246822519)
WEIGHTS = np.stack([
    np.random.default_rng(seed).integers(1, 2**32, size=BLOCK,
                                         dtype=np.uint32) | np.uint32(1)
    for seed in (0xD1657, 0xD1658)])


def _lanes_u32(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a)
    if a.dtype.kind == "f" or a.dtype.name == "bfloat16":
        return np.ascontiguousarray(a, dtype=np.float32).reshape(-1).view(
            np.uint32)
    if a.dtype.itemsize == 4 and a.dtype.kind in "iu":
        return np.ascontiguousarray(a).reshape(-1).view(np.uint32)
    return a.reshape(-1).astype(np.uint32)


def block_lanes(u32: np.ndarray) -> np.ndarray:
    """Flat uint32 elements -> (blocks, 2) uint32 block lanes."""
    pad = (-u32.size) % BLOCK
    x = np.concatenate([u32, np.zeros(pad, np.uint32)]).reshape(-1, BLOCK)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint32(16))) * PRIME
        h = (x.astype(np.uint64) @ WEIGHTS.T.astype(np.uint64)) \
            & np.uint64(0xFFFFFFFF)
        h = h.astype(np.uint32)
        return (h ^ (h >> np.uint32(15))) * PRIME


def leaf_digest(a) -> int:
    """64-bit digest of one leaf (host array)."""
    u32 = _lanes_u32(a)
    if u32.size == 0:
        return 0
    h = block_lanes(u32).astype(np.uint64)
    w = (np.arange(h.shape[0], dtype=np.uint64) * np.uint64(FOLD)
         + np.uint64(1)) & np.uint64(0xFFFFFFFF)
    lo, hi = ((h * w[:, None]).sum(axis=0) & np.uint64(0xFFFFFFFF)).tolist()
    return (int(hi) << 32) | int(lo)


def chunk_key(chunk: bytes) -> int:
    """64-bit content key of one chunk of raw bytes."""
    h = block_lanes(np.frombuffer(chunk, np.uint8).astype(np.uint32))
    words = (h[:, 1].astype(np.uint64) << np.uint64(32)) | h[:, 0]
    d = hashlib.blake2b(words.astype("<u8").tobytes(), digest_size=8)
    d.update(len(chunk).to_bytes(8, "little"))
    return int.from_bytes(d.digest(), "little")


def chunk_spans(n: int, chunk_bytes: int) -> list[tuple[int, int]]:
    """(start, end) of each chunk of an ``n``-byte payload."""
    if n == 0:
        return []
    size = n if chunk_bytes <= 0 or chunk_bytes >= n else chunk_bytes
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]

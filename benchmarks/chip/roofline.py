"""Peaks of the device and the bytes a hash kernel must read.

The hash kernels are bound by memory: each reads its input once and does a
few integer operations per element.  Their least time on a chip is the
bytes the algorithm must read over the chip's HBM bandwidth.  Those bytes
are each hashed leaf's or payload's own size, from its shape and dtype, and
not the size of the uint32 grid the program widens them into: a program
that stops widening then reads as a larger share of the roofline.
"""
from __future__ import annotations

import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def leaf_bytes(shape, dtype) -> int:
    """Bytes of one leaf of ``shape`` and ``dtype``."""
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def hash_least_seconds(nbytes: int, device_kind: str) -> float:
    """Least time the chip needs to read ``nbytes`` once."""
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]

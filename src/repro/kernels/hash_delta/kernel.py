"""On-device block hashing for delta migration (paper §II-D).

The paper detects changed objects by hashing the serialized state on the
host.  TPU adaptation: hash pytree leaves *on device* (one weighted-sum hash
per 1024-element block per lane) so delta detection never pulls full
tensors to the host — only (nb, 2) digests move.  Two independent weight
lanes give each block a 64-bit identity: the chunk store consumes the
per-block vector directly, and ``tensor_digest`` folds it into one leaf hash
on the host.  Position-sensitive via the per-lane weight vectors.

Tiling: each grid step hashes ``ROWS`` block rows at once — a (ROWS, 1024)
uint32 tile, aligned to the TPU's (8, 128) vreg tiling — and writes a
(ROWS, lanes) digest tile.  A row count that is not a multiple of ``ROWS``
leaves a partial last tile; its out-of-range rows are never written back,
and every row's digest depends on that row alone, so they cannot leak.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

PRIME = np.uint32(2654435761)
ROWS = 256            # block rows per grid step: (256, 1024) uint32 = 1 MiB


def premix(x):
    """Per-element mix before the weighted sum.

    The weighted sum alone is linear: a delta confined to one bit (e.g.
    ``conj`` flipping sign bits) contributes ``delta * sum(w)``, which
    annihilates mod 2^32 whenever the affected weights sum even.  The
    xorshift-multiply makes each element's contribution carry-dependent,
    so constant-XOR deltas no longer cancel.  ``premix(0) == 0``, which
    keeps block zero-padding invisible to the hash."""
    x = x ^ (x >> np.uint32(16))
    return x * PRIME


def _row_tile(nb: int) -> int:
    """Rows per grid step: ``ROWS``, or the whole array when it is smaller
    (a block dim equal to the array dim needs no (8, 128) alignment)."""
    return nb if nb <= ROWS else ROWS


_PRIME_I32 = np.int32(PRIME.view(np.int32))


def _hash_rows(x, w):
    """(R, blk) uint32 rows x (lanes, blk) weights -> (R, lanes) digests.

    Mosaic does not reduce unsigned integers, so the arithmetic runs on
    int32 bitcasts with logical shifts: two's-complement add and multiply
    give the same bits as uint32 mod 2^32, so the result equals the uint32
    formulation of :func:`premix` and ``ref.block_hash_ref`` bit for bit."""
    x = jax.lax.bitcast_convert_type(x, jnp.int32)
    w = jax.lax.bitcast_convert_type(w, jnp.int32)
    x = (x ^ jax.lax.shift_right_logical(x, np.int32(16))) * _PRIME_I32
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], w.shape[0]), 1)
    h = jnp.zeros(lane.shape, jnp.int32)
    for l in range(w.shape[0]):
        col = jnp.sum(x * w[l:l + 1, :], axis=1, keepdims=True)   # (R, 1)
        h = jnp.where(lane == l, col, h)
    h = (h ^ jax.lax.shift_right_logical(h, np.int32(15))) * _PRIME_I32
    return jax.lax.bitcast_convert_type(h, jnp.uint32)


def _hash_kernel(x_ref, w_ref, h_ref):
    h_ref[...] = _hash_rows(x_ref[...], w_ref[...])


def block_hash_kernel(x2d_u32, weights, *, interpret: bool = False):
    """x2d (nb, blk) uint32; weights (lanes, blk) uint32 -> (nb, lanes)."""
    nb, blk = x2d_u32.shape
    lanes = weights.shape[0]
    r = _row_tile(nb)
    return pl.pallas_call(
        _hash_kernel,
        grid=(pl.cdiv(nb, r),),
        in_specs=[pl.BlockSpec((r, blk), lambda i: (i, 0)),
                  pl.BlockSpec((lanes, blk), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((r, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, lanes), jnp.uint32),
        interpret=interpret,
    )(x2d_u32, weights)


def _hash_compare_kernel(x_ref, w_ref, p_ref, hp_ref, h_ref, c_ref):
    h = _hash_rows(x_ref[...], w_ref[...])                   # (R, lanes)
    h_ref[...] = h
    diff = jnp.sum((h != p_ref[...]).astype(jnp.int32), axis=1,
                   keepdims=True)                            # (R, 1)
    same = (diff == 0) & (hp_ref[...] != np.uint32(0))
    c_ref[...] = jnp.where(same, np.uint32(0), np.uint32(1))


def block_hash_compare_kernel(x2d_u32, weights, prior, has_prior, *,
                              interpret: bool = False):
    """Fused digest+compare, one launch.

    x2d (nb, blk) uint32; weights (lanes, blk); prior (nb, lanes) is the
    previous manifest's block digest vector; has_prior (nb, 1) uint32 flags
    which rows of ``prior`` are meaningful (0 => block is new, always
    changed).  Returns ``(h, changed)``: the fresh (nb, lanes) digests —
    bit-identical to :func:`block_hash_kernel` — plus a (nb, 1) uint32
    changed flag per block, so the host never re-derives the comparison."""
    nb, blk = x2d_u32.shape
    lanes = weights.shape[0]
    r = _row_tile(nb)
    return pl.pallas_call(
        _hash_compare_kernel,
        grid=(pl.cdiv(nb, r),),
        in_specs=[pl.BlockSpec((r, blk), lambda i: (i, 0)),
                  pl.BlockSpec((lanes, blk), lambda i: (0, 0)),
                  pl.BlockSpec((r, lanes), lambda i: (i, 0)),
                  pl.BlockSpec((r, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((r, lanes), lambda i: (i, 0)),
                   pl.BlockSpec((r, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, lanes), jnp.uint32),
                   jax.ShapeDtypeStruct((nb, 1), jnp.uint32)],
        interpret=interpret,
    )(x2d_u32, weights, prior, has_prior)

"""Block-wise int8 quantize/dequantize as Pallas TPU kernels.

Migration-path compression (paper §II-D "compression ... beyond the scope";
TPU adaptation): tensors are flattened to (nb, 1024) blocks and each block
gets an absmax scale.  Runs on-device so compression does not round-trip
through the host before a migration.

Each grid step covers ``ROWS`` blocks: a (ROWS, 1024) tile, aligned to the
(32, 128) tiling of int8 and so to the (8, 128) tiling of every wider type,
with a (ROWS, 1) scale tile.  Rows past the end of a partial last tile are
never written back, and each row's result depends on that row alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quant_blockwise.ref import INV_127

ROWS = 256


def _row_tile(nb: int) -> int:
    return nb if nb <= ROWS else ROWS


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)        # (R, 1)
    scale = jnp.where(amax > 0, amax * INV_127, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(x_ref.dtype)


def quantize_kernel(x2d, *, interpret: bool = False):
    nb, blk = x2d.shape
    r = _row_tile(nb)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(nb, r),),
        in_specs=[pl.BlockSpec((r, blk), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((r, blk), lambda i: (i, 0)),
                   pl.BlockSpec((r, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, blk), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
    )(x2d)
    return q, s[:, 0]


def dequantize_kernel(q, scale, dtype, *, interpret: bool = False):
    nb, blk = q.shape
    r = _row_tile(nb)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(nb, r),),
        in_specs=[pl.BlockSpec((r, blk), lambda i: (i, 0)),
                  pl.BlockSpec((r, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((r, blk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, blk), dtype),
        interpret=interpret,
    )(q, scale[:, None])

"""Jitted wrappers: quantize/dequantize arbitrary-shape tensors blockwise.

The compiled Pallas kernels run where the data lives on a TPU and the XLA
formulation (``ref.py``) runs elsewhere; both give the same bits.
``interpret`` runs the kernels in interpret mode; only tests set it."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.hash_delta.ops import kernel_mode
from repro.kernels.quant_blockwise.kernel import (
    dequantize_kernel, quantize_kernel,
)
from repro.kernels.quant_blockwise.ref import dequantize_ref, quantize_ref

BLOCK = 1024


@functools.partial(jax.jit, static_argnames=("mode",))
def _quantize(x, *, mode: str):
    n = int(np.prod(x.shape))
    pad = (-n) % BLOCK
    x2d = jnp.pad(x.reshape(-1), (0, pad)).reshape(-1, BLOCK)
    if mode == "xla" or x2d.shape[0] == 0:
        return quantize_ref(x2d)
    return quantize_kernel(x2d, interpret=mode == "interpret")


def quantize(x, *, interpret: bool = False):
    """Any-shape float tensor -> (q int8 (nb, BLOCK), scales (nb,)).

    Pads the flattened tensor to a BLOCK multiple; the caller keeps the
    true shape."""
    return _quantize(x, mode=kernel_mode(x, interpret))


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "mode"))
def _dequantize(q, scales, *, shape: tuple, dtype, mode: str):
    if mode == "xla" or q.shape[0] == 0:
        x2d = dequantize_ref(q, scales, dtype)
    else:
        x2d = dequantize_kernel(q, scales, dtype,
                                interpret=mode == "interpret")
    n = int(np.prod(shape))
    return x2d.reshape(-1)[:n].reshape(shape)


def dequantize(q, scales, shape: tuple, dtype=jnp.bfloat16, *,
               interpret: bool = False):
    return _dequantize(q, scales, shape=tuple(shape), dtype=dtype,
                       mode=kernel_mode(q, interpret))

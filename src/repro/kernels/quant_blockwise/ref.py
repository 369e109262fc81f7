"""Pure-jnp oracle for block-wise int8 quantization."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# absmax -> scale multiplies by a rounded reciprocal: XLA already rewrites
# ``amax / 127`` that way on some backends and not on others, so spelling
# it out keeps the kernel and this oracle bit-identical everywhere
INV_127 = np.float32(1.0 / 127.0)


def quantize_ref(x2d):
    """x2d (nb, block) fp -> (q int8 (nb, block), scales fp32 (nb,))."""
    xf = x2d.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=1)
    scale = jnp.where(amax > 0, amax * INV_127, 1.0)
    q = jnp.clip(jnp.round(xf / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_ref(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale[:, None]).astype(dtype)

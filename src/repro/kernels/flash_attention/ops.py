"""Jitted public wrapper for the flash-attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "impl"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    impl: str = "pallas"):
    """q (B,H,S,hd); k/v (B,KV,S,hd) -> (B,H,S,hd).

    impl="pallas": the TPU kernel (tests validate it on CPU in interpret mode).
    impl="xla": the pure-jnp oracle.
    """
    if impl == "xla":
        return attention_ref(q, k, v, causal=causal)
    return flash_attention_kernel(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)

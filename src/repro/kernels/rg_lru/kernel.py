"""RG-LRU linear recurrence as a Pallas TPU kernel.

TPU adaptation: Griffin's GPU kernel is a warp-parallel scan;
on TPU we run the recurrence sequentially over sequence blocks — grid
(B, W/bw, S/bs) with the sequence axis innermost — carrying the (1, bw)
hidden state in VMEM scratch.  Inside a block the recurrence over ``bs``
steps runs as a fori_loop on VREG rows (bw lanes wide), which is exactly the
shape the VPU wants; the channel axis is the parallel axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, y_ref, h_scratch, *, bs: int):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    a = a_ref[0].astype(jnp.float32)     # (bs, bw)
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h = carry
        h = a[t][None, :] * h + b[t][None, :]
        y_ref[0, t, :] = h[0].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, step, h_scratch[...])
    h_scratch[...] = h


def rglru_scan_kernel(a, b, *, block_s: int = 256, block_w: int = 256,
                      interpret: bool = False):
    """a/b (B,S,W) -> h (B,S,W). Zero initial state (match model prefill)."""
    B, S, W = a.shape
    bs = min(block_s, S)
    bw = min(block_w, W)
    assert S % bs == 0 and W % bw == 0, (S, W, bs, bw)
    grid = (B, W // bw, S // bs)  # sequence innermost: sequential carry

    kernel = functools.partial(_rglru_kernel, bs=bs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(a, b)

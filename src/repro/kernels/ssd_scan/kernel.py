"""Mamba-2 SSD chunk scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm: the GPU implementation
leans on warp-level parallel scans; on TPU we exploit the *sequential* grid
traversal instead — grid (B, H, num_chunks) with chunks innermost, carrying
the (P, N) inter-chunk state in VMEM scratch, so the whole scan is one
pallas_call with MXU matmuls for the intra-chunk quadratic term.

Inputs are pre-arranged by ops.py:
  xdt   (B, H, nc, Q, P)   x * dt
  Bm    (B, nc, Q, N)      B after conv (shared across heads, one group)
  Cm    (B, nc, Q, N)
  cums  (B, H, nc, Q)      within-chunk cumsum of dt*A
Output y (B, H, nc, Q, P) and final state (B, H, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, b_ref, c_ref, cums_ref, y_ref, state_out_ref, s_scratch,
                *, Q: int, P: int, N: int):
    c_idx = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)       # (Q, P)
    Bm = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)             # (Q, N)
    cums = cums_ref[0, 0, 0].astype(jnp.float32)     # (Q,)

    # intra-chunk: (C B^T ∘ L) @ xdt with L_ij = exp(cums_i - cums_j) tril
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    li = cums[:, None] - cums[None, :]
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(iota_i >= iota_j, jnp.exp(li), 0.0)
    y_intra = jax.lax.dot(CB * L, xdt, preferred_element_type=jnp.float32)

    # inter-chunk: C_i exp(cums_i) @ S_prev^T
    s_prev = s_scratch[...]                          # (P, N)
    Cexp = Cm * jnp.exp(cums)[:, None]
    y_inter = jax.lax.dot_general(Cexp, s_prev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (Q,P)

    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S_new = exp(cums_last) * S_prev + xdt^T @ (B * dec_end)
    last = cums[Q - 1]
    dec_end = jnp.exp(last - cums)                   # (Q,)
    delta = jax.lax.dot_general(xdt, Bm * dec_end[:, None],
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P,N)
    s_new = jnp.exp(last) * s_prev + delta
    s_scratch[...] = s_new

    @pl.when(c_idx == nc - 1)
    def _final():
        state_out_ref[0, 0] = s_new.astype(state_out_ref.dtype)


def ssd_scan_kernel(xdt, Bm, Cm, cums, *, interpret: bool = False):
    B, H, nc, Q, P = xdt.shape
    N = Bm.shape[-1]
    grid = (B, H, nc)
    kernel = functools.partial(_ssd_kernel, Q=Q, P=P, N=N)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), xdt.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, Bm, Cm, cums)
    return y, state

"""Mamba-2 block: SSD (state-space duality) chunked algorithm [arXiv:2405.21060].

XLA reference path (used by train and prefill); the Pallas TPU kernel in
:mod:`repro.kernels.ssd_scan` implements the same chunk-sequential algorithm
with the running state carried in VMEM scratch.

Shapes: x (B,S,H,P)  dt (B,S,H)  A (H,)<0  B_in/C_in (B,S,N) (one group).
Chunked: intra-chunk quadratic term + inter-chunk linear recurrence over
chunk states (H,P,N).  Decays computed in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.context import shard
from repro.models.layers import P, causal_conv1d, rms_norm, silu


def ssm_spec(cfg):
    d, din, H, N, W = (cfg.d_model, cfg.d_inner, cfg.ssm_heads,
                       cfg.ssm_state, cfg.conv_width)
    return {
        "w_z": P((d, din), ("embed", "ssm_inner")),
        "w_x": P((d, din), ("embed", "ssm_inner")),
        "w_B": P((d, N), ("embed", "ssm_state")),
        "w_C": P((d, N), ("embed", "ssm_state")),
        "w_dt": P((d, H), ("embed", "ssm_heads")),
        "conv_w": P((din + 2 * N, W), ("conv", None)),
        **({"conv_b": P((din + 2 * N,), ("conv",), scale=0.02)}
           if cfg.conv_bias else {}),
        "dt_bias": P((H,), ("ssm_heads",), init="dt_bias"),
        "A_log": P((H,), ("ssm_heads",), init="a_log"),
        "D": P((H,), ("ssm_heads",), init="ones"),
        "norm_w": P((din,), ("ssm_inner",), init="zeros"),
        "w_out": P((din, d), ("ssm_inner", "embed")),
    }


def _segsum(dA):
    """dA (..., Q) -> cumulative sums; returns cums (..., Q) from chunk start."""
    return jnp.cumsum(dA, axis=-1)


def ssd_chunked(x, dt, A, B_in, C_in, chunk: int, init_state=None):
    """Returns (y, final_state).

    x (B,S,H,P)  dt (B,S,H) (post-softplus)  A (H,)  B_in/C_in (B,S,N).
    """
    Bz, S, H, Pd = x.shape
    N = B_in.shape[-1]
    Q = min(chunk, S)
    if S % Q:  # pad to a chunk multiple; dt=0 on pads => decay 1, contribution 0
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_in = jnp.pad(B_in, ((0, 0), (0, pad), (0, 0)))
        C_in = jnp.pad(C_in, ((0, 0), (0, pad), (0, 0)))
        y, final = ssd_chunked(x, dt, A, B_in, C_in, chunk, init_state)
        return y[:, :S], final
    nc = S // Q

    xc = x.reshape(Bz, nc, Q, H, Pd)
    dtc = dt.reshape(Bz, nc, Q, H).astype(jnp.float32)
    Bc = B_in.reshape(Bz, nc, Q, N)
    Cc = C_in.reshape(Bz, nc, Q, N)

    dA = dtc * A.astype(jnp.float32)                    # (B,nc,Q,H)
    cums = _segsum(jnp.swapaxes(dA, -1, -2))            # (B,nc,H,Q)
    cums = jnp.swapaxes(cums, -1, -2)                   # (B,nc,Q,H)

    # ---- intra-chunk (quadratic in Q) ----
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc, preferred_element_type=jnp.float32)
    # mask the exponent, not the product: above the diagonal the difference
    # is positive and exp overflows at real chunk sizes, and a masked-out
    # inf still turns the backward pass into 0 * inf = NaN
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    seg = cums[:, :, :, None, :] - cums[:, :, None, :, :]           # (B,nc,i,j,H)
    Lmat = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    M = CB[..., None] * Lmat * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M.astype(x.dtype), xc)

    # ---- chunk states ----
    dec_end = jnp.exp(cums[:, :, -1:, :] - cums)        # (B,nc,Q,H)
    wts = (dec_end * dtc).astype(x.dtype)
    states = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", wts, Bc.astype(x.dtype), xc)

    # ---- inter-chunk recurrence over chunk states ----
    chunk_decay = jnp.exp(dA.sum(axis=2))               # (B,nc,H)
    s0 = (jnp.zeros((Bz, H, Pd, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(carry, inp):
        dec, st = inp
        prev = carry
        new = dec[:, :, None, None] * prev + st.astype(jnp.float32)
        return new, prev

    final, prevs = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    prevs = jnp.moveaxis(prevs, 0, 1)                   # (B,nc,H,P,N) state before chunk

    dec_in = jnp.exp(cums).astype(x.dtype)              # decay from chunk start
    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", Cc.astype(x.dtype),
                         prevs.astype(x.dtype)) * dec_in[..., None]

    y = (y_intra + y_inter).reshape(Bz, S, H, Pd)
    return y, final.astype(x.dtype)


def ssm_forward(p, x_res, cfg, ctx=None, conv_state=None, ssm_state=None):
    """Full mamba2 mixer. x_res (B,S,d) -> (y (B,S,d), (conv_state, ssm_state))."""
    B, S, d = x_res.shape
    din, H, N = cfg.d_inner, cfg.ssm_heads, cfg.ssm_state
    Pd = cfg.ssm_headdim

    z = jnp.einsum("bsd,di->bsi", x_res, p["w_z"])
    xb = jnp.einsum("bsd,di->bsi", x_res, p["w_x"])
    Bv = jnp.einsum("bsd,dn->bsn", x_res, p["w_B"])
    Cv = jnp.einsum("bsd,dn->bsn", x_res, p["w_C"])
    dt_raw = jnp.einsum("bsd,dh->bsh", x_res, p["w_dt"])

    conv_in = jnp.concatenate([xb, Bv, Cv], axis=-1)
    conv_out, new_conv = causal_conv1d(conv_in, p["conv_w"], conv_state,
                                       bias=p.get("conv_b"))
    xb, Bv, Cv = jnp.split(conv_out, [din, din + N], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    xh = xb.reshape(B, S, H, Pd)
    xh = shard(ctx, xh, "batch", "seq", "ssm_heads", None)
    y, final_state = ssd_chunked(xh, dt, A, Bv, Cv, cfg.ssm_chunk, ssm_state)
    y = y + xh * p["D"].astype(x_res.dtype)[None, None, :, None]

    y = y.reshape(B, S, din)
    y = rms_norm(y * silu(z), p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"])
    return out, (new_conv, final_state)


def ssm_decode_step(p, x_res, cfg, conv_state, ssm_state, ctx=None):
    """One-token decode. x_res (B,1,d); conv_state (B,W-1,C); ssm_state (B,H,P,N)."""
    B = x_res.shape[0]
    din, H, N, Pd = cfg.d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim

    z = jnp.einsum("bsd,di->bsi", x_res, p["w_z"])
    xb = jnp.einsum("bsd,di->bsi", x_res, p["w_x"])
    Bv = jnp.einsum("bsd,dn->bsn", x_res, p["w_B"])
    Cv = jnp.einsum("bsd,dn->bsn", x_res, p["w_C"])
    dt_raw = jnp.einsum("bsd,dh->bsh", x_res, p["w_dt"])

    conv_in = jnp.concatenate([xb, Bv, Cv], axis=-1)
    conv_out, new_conv = causal_conv1d(conv_in, p["conv_w"], conv_state,
                                       bias=p.get("conv_b"))
    xb, Bv, Cv = jnp.split(conv_out, [din, din + N], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt[:, 0, :] * A)                       # (B,H)

    xh = xb[:, 0].reshape(B, H, Pd)
    contrib = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0, :].astype(x_res.dtype),
                         Bv[:, 0].astype(x_res.dtype), xh)
    new_state = dA[:, :, None, None].astype(x_res.dtype) * ssm_state + contrib
    y = jnp.einsum("bn,bhpn->bhp", Cv[:, 0], new_state)
    y = y + xh * p["D"].astype(x_res.dtype)[None, :, None]

    y = y.reshape(B, 1, din)
    y = rms_norm(y * silu(z), p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"])
    return out, (new_conv, new_state)

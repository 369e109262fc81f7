"""GQA attention: train/prefill paths (full causal, chunked-causal, banded
local window) and decode paths (plain, and sequence-parallel via shard_map in
``repro.distributed.decode_attn``).

All softmax arithmetic is fp32; masks use -1e30 (never -inf) so that empty
rows stay NaN-free.  A Pallas flash-attention kernel
(:mod:`repro.kernels.flash_attention`) is selectable with ``impl="pallas"``
on real TPUs; the XLA paths below are what the CPU dry-run lowers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import P, apply_rope, head_rms_norm

NEG = -1e30


def attn_spec(cfg):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    spec = {
        "wq": P((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": P((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((H, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        spec["q_norm"] = P((hd,), ("head_dim",), init="zeros")
        spec["k_norm"] = P((hd,), ("head_dim",), init="zeros")
    return spec


def qkv_project(p, x, cfg, positions):
    """x (B,S,d) -> q (B,S,H,hd), k/v (B,S,KV,hd) with rope + optional qk-norm."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, rope_pct=cfg.rope_pct, theta=cfg.rope_theta)
        k = apply_rope(k, positions, rope_pct=cfg.rope_pct, theta=cfg.rope_theta)
    return q, k, v


def _group(q, KV):
    """(B,S,H,hd) -> (B,S,KV,G,hd)."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, KV, H // KV, hd)


def full_causal_attention(q, k, v, *, chunk_q: int = 1024,
                          scale: float | None = None):
    """Chunked causal attention (flash-style at the XLA level).

    Scans over query chunks so the (chunk, S) score block is the only
    transient — keeps prefill_32k within HBM without a kernel.  Note: each
    chunk still computes scores against all S keys (masked), i.e. ~2x the
    causal-ideal FLOPs; the Pallas kernel closes that gap on real TPUs
    (EXPERIMENTS.md §Perf).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qg = _group(q, KV)                      # (B,S,KV,G,hd)
    scale = scale or hd ** -0.5
    nq = max(S // min(chunk_q, S), 1)
    cq = S // nq
    qb = qg.reshape(B, nq, cq, KV, H // KV, hd)
    kpos = jnp.arange(S)

    def body(_, qi_i):
        qi, i = qi_i
        qpos = i * cq + jnp.arange(cq)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qi, k).astype(jnp.float32) * scale
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, None], s, NEG)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return None, jnp.einsum("bkgqs,bskh->bqkgh", w, v)

    _, ob = jax.lax.scan(body, None, (jnp.moveaxis(qb, 1, 0), jnp.arange(nq)))
    out = jnp.moveaxis(ob, 0, 1).reshape(B, S, H, hd)
    return out


def banded_local_attention(q, k, v, *, window: int):
    """Sliding-window causal attention, O(S*W): block i attends {i-1, i}.

    Requires S % window == 0.  Used by recurrentgemma's local-attention
    layers (train/prefill); FLOPs stay linear in S (long_500k viability).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    W = window
    assert S % W == 0, (S, W)
    nb = S // W
    qg = _group(q, KV).reshape(B, nb, W, KV, H // KV, hd)
    kb = k.reshape(B, nb, W, KV, hd)
    vb = v.reshape(B, nb, W, KV, hd)
    zpad = jnp.zeros_like(kb[:, :1])
    kcat = jnp.concatenate([jnp.concatenate([zpad, kb[:, :-1]], 1), kb], axis=2)  # (B,nb,2W,KV,hd)
    vcat = jnp.concatenate([jnp.concatenate([zpad, vb[:, :-1]], 1), vb], axis=2)
    scale = hd ** -0.5
    s = jnp.einsum("bnqkgh,bnskh->bnkgqs", qg, kcat).astype(jnp.float32) * scale
    iq = jnp.arange(W)[:, None]
    j = jnp.arange(2 * W)[None, :]
    diff = (W + iq) - j
    win = (diff >= 0) & (diff < W)                      # causal window
    blk = jnp.arange(nb)[:, None, None]
    valid = win[None] & ((blk > 0) | (j[None] >= W))    # block 0 has no prev
    s = jnp.where(valid[None, :, None, None], s, NEG)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bnkgqs,bnskh->bnqkgh", w, vcat)
    return out.reshape(B, S, H, hd)


# ----------------------------------------------------------------------
# Decode (one token, cache) — plain path.  SP path: distributed/decode_attn.
# ----------------------------------------------------------------------

def decode_attention_plain(q, k_cache, v_cache, pos, scale: float | None = None):
    """q (B,1,H,hd); caches (B,KV,S,hd); pos (B,) index of the CURRENT token
    (caches already contain the current token at ``pos``).  ``scale``
    multiplies the scores (default 1/sqrt(hd))."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[1]
    S = k_cache.shape[2]
    qg = q.reshape(B, KV, H // KV, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, k_cache).astype(jnp.float32) * (
        scale or hd ** -0.5)
    valid = jnp.arange(S)[None, :] <= pos[:, None]      # (B,S)
    s = jnp.where(valid[:, None, None, :], s, NEG)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bksh->bkgh", w, v_cache)
    return out.reshape(B, 1, H, hd)


def cache_write_plain(k_cache, v_cache, new_k, new_v, pos):
    """Write (B,KV,1,hd) new entries at per-sequence position ``pos`` (B,)."""
    def upd(c, n, p):
        return jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=1)
    k2 = jax.vmap(upd)(k_cache, jnp.swapaxes(new_k, 1, 2), pos)
    v2 = jax.vmap(upd)(v_cache, jnp.swapaxes(new_v, 1, 2), pos)
    return k2, v2


def decode_attention_window(q, k_cache, v_cache, pos, *, window: int):
    """Ring-buffer sliding-window decode (recurrentgemma local-attn layers).

    Caches (B,KV,W,hd); slot of token p is p % W; valid keys are the last
    ``window`` positions <= pos.
    """
    B, _, H, hd = q.shape
    KV, W = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(B, KV, H // KV, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, k_cache).astype(jnp.float32) * (hd ** -0.5)
    slot = jnp.arange(W)[None, :]
    p = pos[:, None]
    # global position stored in slot j: the largest q <= pos with q % W == j
    gpos = p - ((p - slot) % W)
    valid = (gpos >= 0) & (gpos >= p - (window - 1))
    s = jnp.where(valid[:, None, None, :], s, NEG)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bkgs,bksh->bkgh", w, v_cache).reshape(B, 1, H, hd)


def cache_write_window(k_cache, v_cache, new_k, new_v, pos):
    W = k_cache.shape[2]
    return cache_write_plain(k_cache, v_cache, new_k, new_v, pos % W)


# ----------------------------------------------------------------------
# Cross attention (whisper decoder): static memory, no cache writes.
# ----------------------------------------------------------------------

def cross_attention(q, k_mem, v_mem):
    B, S, H, hd = q.shape
    KV = k_mem.shape[2]
    qg = _group(q, KV)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_mem).astype(jnp.float32) * (hd ** -0.5)
    w = jax.nn.softmax(s, axis=-1).astype(v_mem.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v_mem).reshape(B, S, H, hd)

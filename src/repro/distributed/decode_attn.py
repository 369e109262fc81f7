"""Sequence-parallel decode attention (shard_map over the "model" axis).

Decode KV caches are sharded along the *sequence* axis over "model":
each shard holds S/m cache slots, computes a partial
flash-style (m, l, o) against its slice, and the partials merge with a
log-sum-exp psum.  This is what lets 32k x 128-batch caches fit v5e HBM
(e.g. qwen3-moe: 806 GB global -> 1.6 GB/chip) without replicating KV heads.

The new token's K/V is written only on the shard owning slot ``pos``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

NEG = -1e30


def sp_decode_attention(ctx, q, k_cache, v_cache, new_k, new_v, pos):
    """q (B,1,H,hd); caches (B,KV,S,hd) seq-sharded; new_k/new_v (B,S=1,KV,hd);
    pos (B,). Returns (out (B,1,H,hd), k_cache, v_cache)."""
    mesh = ctx.mesh
    batch = ctx.rules["batch"]
    rep = PS(batch, None, None, None)
    cache_spec = PS(batch, None, "model", None)

    def shard_fn(q, k, v, nk, nv, pos):
        Bl, _, H, hd = q.shape
        KV, Sl = k.shape[1], k.shape[2]
        G = H // KV
        s_idx = jax.lax.axis_index("model")

        # ---- write the new token on the owning shard ----
        nk = jnp.swapaxes(nk, 1, 2)  # (Bl,KV,1,hd)
        nv = jnp.swapaxes(nv, 1, 2)
        tgt = pos // Sl
        off = (pos % Sl).astype(jnp.int32)

        def upd(c, n, o, w):
            u = jax.lax.dynamic_update_slice_in_dim(c, n, o, axis=1)
            return jnp.where(w, u, c)

        write = tgt == s_idx
        k = jax.vmap(upd)(k, nk, off, write)
        v = jax.vmap(upd)(v, nv, off, write)

        # ---- partial attention on the local slice ----
        qg = q.reshape(Bl, KV, G, hd)
        s = jnp.einsum("bkgh,bksh->bkgs", qg, k).astype(jnp.float32) * (hd ** -0.5)
        kpos = s_idx * Sl + jnp.arange(Sl)
        valid = kpos[None, :] <= pos[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG)

        m_l = jnp.max(s, axis=-1)                        # (Bl,KV,G)
        p = jnp.exp(s - m_l[..., None])
        l_l = jnp.sum(p, axis=-1)
        o_l = jnp.einsum("bkgs,bksh->bkgh", p.astype(v.dtype), v).astype(jnp.float32)

        # ---- merge partials across shards (flash-style LSE combine) ----
        m_g = jax.lax.pmax(m_l, "model")
        c = jnp.exp(m_l - m_g)
        l_g = jax.lax.psum(l_l * c, "model")
        o_g = jax.lax.psum(o_l * c[..., None], "model")
        out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
        return out.reshape(Bl, 1, H, hd), k, v

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(rep, cache_spec, cache_spec, rep, rep, PS(batch)),
        out_specs=(rep, cache_spec, cache_spec),
    )
    return fn(q, k_cache, v_cache, new_k, new_v, pos)

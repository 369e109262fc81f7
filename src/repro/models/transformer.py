"""Decoder LM stack: scan-over-layers for every family (dense / moe / ssm /
hybrid / vlm), with train, prefill and decode paths and pytree KV/state caches.

Layer parameters are stacked on a leading "layers" (or "groups") axis so the
HLO stays small regardless of depth (94-layer qwen3 compiles as one scanned
block) — essential for dry-run compile times and standard MaxText practice.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.context import shard
from repro.models import attention as attn
from repro.models import griffin, moe, ssm
from repro.models.layers import (
    P, rms_norm, stack_spec, swiglu,
)

Axes = tuple


# ======================================================================
# Param specs
# ======================================================================

def mlp_spec(cfg):
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "w_gate": P((d, ff), ("embed", "mlp")),
        "w_up": P((d, ff), ("embed", "mlp")),
        "w_down": P((ff, d), ("mlp", "embed")),
    }


def ffn_spec(cfg):
    return moe.moe_spec(cfg) if cfg.num_experts else mlp_spec(cfg)


def layer_spec(cfg, kind: str):
    d = cfg.d_model
    ln = lambda: P((d,), ("embed",), init="zeros")
    if kind == "ssm":
        spec = {"ln": ln(), "mixer": ssm.ssm_spec(cfg)}
        if cfg.d_ff:   # granite: the mixer is followed by an FFN
            spec.update(ln2=ln(), ffn=ffn_spec(cfg))
        return spec
    if kind == "rec":
        return {"ln1": ln(), "mixer": griffin.rglru_spec(cfg),
                "ln2": ln(), "mlp": mlp_spec(cfg)}
    return {"ln1": ln(), "attn": attn.attn_spec(cfg), "ln2": ln(),
            "ffn": ffn_spec(cfg)}


def decoder_spec(cfg):
    kinds = cfg.layer_kinds()
    L = cfg.num_layers
    if len(set(kinds)) == 1:
        return {"stack": stack_spec(layer_spec(cfg, kinds[0]), L)}
    pat = cfg.block_pattern
    G = L // len(pat)
    tail_kinds = kinds[G * len(pat):]
    group = {f"b{i}_{k}": layer_spec(cfg, k) for i, k in enumerate(pat)}
    spec: dict[str, Any] = {"groups": stack_spec(group, G, "groups")}
    if tail_kinds:
        spec["tail"] = {f"t{i}_{k}": layer_spec(cfg, k) for i, k in enumerate(tail_kinds)}
    return spec


# ======================================================================
# Single blocks (train/prefill mode)
# ======================================================================

def _o_proj(o, wo):
    return jnp.einsum("bshk,hkd->bsd", o, wo)


def _residual(x, y, cfg):
    """``x`` plus the branch ``y``, scaled by the residual multiplier."""
    m = cfg.residual_multiplier
    return x + (y if m == 1.0 else y * m)


def _ffn_block(lp, x, cfg, ctx):
    """Pre-norm FFN on the residual stream: the MoE (with its shared
    expert) or a dense SwiGLU.  Returns (x, aux)."""
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.num_experts:
        f, aux = moe.moe_ffn(lp["ffn"], h, cfg, ctx)
    else:
        f, aux = swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                        lp["ffn"]["w_down"]), 0.0
    return _residual(x, f, cfg), aux


def attn_block_fwd(lp, x, cfg, ctx, positions, *, window: int = 0,
                   want_cache: bool = False, cache_len: int | None = None):
    """Returns (x, aux, cache_entry)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(lp["attn"], h, cfg, positions)
    S = x.shape[1]
    if window and S > window and S % window == 0:
        o = attn.banded_local_attention(q, k, v, window=window)
    else:
        cq = S if cfg.exact_costs else 1024
        o = attn.full_causal_attention(q, k, v, chunk_q=cq,
                                       scale=cfg.attention_multiplier)
    x = _residual(x, _o_proj(o, lp["attn"]["wo"]), cfg)
    x = shard(ctx, x, "batch", "seq", None)

    x, aux = _ffn_block(lp, x, cfg, ctx)
    x = shard(ctx, x, "batch", "seq", None)

    entry = None
    if want_cache:
        target = min(window, cache_len or S) if window else (cache_len or S)
        if window and S >= window:
            # ring layout: global position p lives in slot p % W
            shift = (S - window) % window
            kc = jnp.swapaxes(jnp.roll(k[:, -window:], shift, axis=1), 1, 2)
            vc = jnp.swapaxes(jnp.roll(v[:, -window:], shift, axis=1), 1, 2)
        else:
            kc, vc = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
        if target > kc.shape[2]:  # pre-allocate future decode slots
            pad = ((0, 0), (0, 0), (0, target - kc.shape[2]), (0, 0))
            kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
        entry = {"k": kc, "v": vc}
    return x, aux, entry


def ssm_block_fwd(lp, x, cfg, ctx, *, want_cache: bool = False):
    h = rms_norm(x, lp["ln"], cfg.norm_eps)
    y, (conv_st, ssm_st) = ssm.ssm_forward(lp["mixer"], h, cfg, ctx)
    x = _residual(x, y, cfg)
    x = shard(ctx, x, "batch", "seq", None)
    aux = 0.0
    if "ffn" in lp:
        x, aux = _ffn_block(lp, x, cfg, ctx)
        x = shard(ctx, x, "batch", "seq", None)
    entry = {"conv": conv_st, "ssm": ssm_st} if want_cache else None
    return x, aux, entry


def rec_block_fwd(lp, x, cfg, ctx, *, want_cache: bool = False):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    y, (conv_st, lru_st) = griffin.recurrent_forward(lp["mixer"], h, cfg, ctx)
    x = x + y
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + swiglu(h2, lp["mlp"]["w_gate"], lp["mlp"]["w_up"], lp["mlp"]["w_down"])
    x = shard(ctx, x, "batch", "seq", None)
    entry = {"conv": conv_st, "lru": lru_st} if want_cache else None
    return x, 0.0, entry


def block_fwd(kind, lp, x, cfg, ctx, positions, want_cache, cache_len=None):
    if kind == "ssm":
        return ssm_block_fwd(lp, x, cfg, ctx, want_cache=want_cache)
    if kind == "rec":
        return rec_block_fwd(lp, x, cfg, ctx, want_cache=want_cache)
    window = cfg.local_window if cfg.block_pattern else 0
    return attn_block_fwd(lp, x, cfg, ctx, positions, window=window,
                          want_cache=want_cache, cache_len=cache_len)


# ======================================================================
# Single blocks (decode mode)
# ======================================================================

def attn_block_dec(lp, x, cfg, ctx, pos, cache, *, window: int = 0):
    from repro.distributed.decode_attn import sp_decode_attention

    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(lp["attn"], h, cfg, pos[:, None])
    if window:
        kc, vc = attn.cache_write_window(cache["k"], cache["v"], k, v, pos)
        o = attn.decode_attention_window(q, kc, vc, pos, window=window)
    elif ctx is not None and ctx.sp_decode:
        o, kc, vc = sp_decode_attention(ctx, q, cache["k"], cache["v"], k, v, pos)
    else:
        kc, vc = attn.cache_write_plain(cache["k"], cache["v"], k, v, pos)
        o = attn.decode_attention_plain(q, kc, vc, pos,
                                        scale=cfg.attention_multiplier)
    x = _residual(x, _o_proj(o, lp["attn"]["wo"]), cfg)
    x, _ = _ffn_block(lp, x, cfg, ctx)
    return x, {"k": kc, "v": vc}


def ssm_block_dec(lp, x, cfg, ctx, cache):
    h = rms_norm(x, lp["ln"], cfg.norm_eps)
    y, (conv_st, ssm_st) = ssm.ssm_decode_step(
        lp["mixer"], h, cfg, cache["conv"], cache["ssm"], ctx)
    x = _residual(x, y, cfg)
    if "ffn" in lp:
        x, _ = _ffn_block(lp, x, cfg, ctx)
    return x, {"conv": conv_st, "ssm": ssm_st}


def rec_block_dec(lp, x, cfg, ctx, cache):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    y, (conv_st, lru_st) = griffin.recurrent_forward(
        lp["mixer"], h, cfg, ctx, conv_state=cache["conv"],
        lru_state=cache["lru"], decode=True)
    x = x + y
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + swiglu(h2, lp["mlp"]["w_gate"], lp["mlp"]["w_up"], lp["mlp"]["w_down"])
    return x, {"conv": conv_st, "lru": lru_st}


def block_dec(kind, lp, x, cfg, ctx, pos, cache):
    if kind == "ssm":
        return ssm_block_dec(lp, x, cfg, ctx, cache)
    if kind == "rec":
        return rec_block_dec(lp, x, cfg, ctx, cache)
    window = cfg.local_window if cfg.block_pattern else 0
    return attn_block_dec(lp, x, cfg, ctx, pos, cache, window=window)


# ======================================================================
# Stacked decoder forward
# ======================================================================

def _remat_wrap(f, remat: str):
    if remat == "none":
        return f
    policy = (jax.checkpoint_policies.dots_saveable if remat == "dots"
              else jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(f, policy=policy)


def decoder_forward(params, x, cfg, ctx, positions, *, remat: str = "none",
                    want_cache: bool = False, cache_len: int | None = None):
    """Returns (x, aux_total, cache_or_None)."""
    kinds = cfg.layer_kinds()

    if "stack" in params:
        kind = kinds[0]

        def body(carry, lp):
            x, aux = carry
            x, a, entry = block_fwd(kind, lp, x, cfg, ctx, positions, want_cache,
                                    cache_len)
            return (x, aux + a), entry

        if cfg.exact_costs:  # unrolled python loop: exact HLO cost analysis
            step = _remat_wrap(body, remat)
            aux, entries_l = 0.0, []
            for i in range(cfg.num_layers):
                lp = jax.tree_util.tree_map(lambda a: a[i], params["stack"])
                (x, aux), e = step((x, aux), lp)
                entries_l.append(e)
            entries = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *entries_l)
                       if want_cache else None)
            return x, aux, ({"stack": entries} if want_cache else None)

        (x, aux), entries = jax.lax.scan(
            _remat_wrap(body, remat), (x, 0.0), params["stack"])
        return x, aux, ({"stack": entries} if want_cache else None)

    # hybrid: scan over pattern groups, then unrolled tail
    pat = cfg.block_pattern
    names = [f"b{i}_{k}" for i, k in enumerate(pat)]

    def gbody(carry, gp):
        x, aux = carry
        entries = {}
        for name, kind in zip(names, pat):
            x, a, e = block_fwd(kind, gp[name], x, cfg, ctx, positions,
                                want_cache, cache_len)
            aux = aux + a
            entries[name] = e
        return (x, aux), entries

    if cfg.exact_costs:
        G = cfg.num_layers // len(pat)
        gstep = _remat_wrap(gbody, remat)
        aux, gl = 0.0, []
        for i in range(G):
            gp = jax.tree_util.tree_map(lambda a: a[i], params["groups"])
            (x, aux), ge = gstep((x, aux), gp)
            gl.append(ge)
        gentries = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *gl)
                    if want_cache else None)
    else:
        (x, aux), gentries = jax.lax.scan(
            _remat_wrap(gbody, remat), (x, 0.0), params["groups"])

    tentries = {}
    if "tail" in params:
        G = cfg.num_layers // len(pat)
        tail_kinds = kinds[G * len(pat):]
        for i, kind in enumerate(tail_kinds):
            name = f"t{i}_{kind}"
            x, a, e = block_fwd(kind, params["tail"][name], x, cfg, ctx,
                                positions, want_cache, cache_len)
            aux = aux + a
            tentries[name] = e
    cache = {"groups": gentries, "tail": tentries} if want_cache else None
    return x, aux, cache


def decoder_decode(params, x, cfg, ctx, pos, cache):
    """One-token decode through the stack. Returns (x, new_cache)."""
    kinds = cfg.layer_kinds()

    if "stack" in params:
        kind = kinds[0]

        def body(x, lp_cache):
            lp, c = lp_cache
            x, nc = block_dec(kind, lp, x, cfg, ctx, pos, c)
            return x, nc

        if cfg.exact_costs:
            outs = []
            for i in range(cfg.num_layers):
                sl = jax.tree_util.tree_map(lambda a: a[i],
                                            (params["stack"], cache["stack"]))
                x, nc = body(x, sl)
                outs.append(nc)
            new_entries = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
            return x, {"stack": new_entries}

        x, new_entries = jax.lax.scan(body, x, (params["stack"], cache["stack"]))
        return x, {"stack": new_entries}

    pat = cfg.block_pattern
    names = [f"b{i}_{k}" for i, k in enumerate(pat)]

    def gbody(x, gp_c):
        gp, gc = gp_c
        out = {}
        for name, kind in zip(names, pat):
            x, out[name] = block_dec(kind, gp[name], x, cfg, ctx, pos, gc[name])
        return x, out

    if cfg.exact_costs:
        G = cfg.num_layers // len(pat)
        outs = []
        for i in range(G):
            sl = jax.tree_util.tree_map(lambda a: a[i],
                                        (params["groups"], cache["groups"]))
            x, nc = gbody(x, sl)
            outs.append(nc)
        g_new = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
    else:
        x, g_new = jax.lax.scan(gbody, x, (params["groups"], cache["groups"]))

    t_new = {}
    if "tail" in params:
        G = cfg.num_layers // len(pat)
        tail_kinds = kinds[G * len(pat):]
        for i, kind in enumerate(tail_kinds):
            name = f"t{i}_{kind}"
            x, t_new[name] = block_dec(kind, params["tail"][name], x, cfg, ctx,
                                       pos, cache["tail"][name])
    return x, {"groups": g_new, "tail": t_new}


# ======================================================================
# Cache construction (zeros; abstract under jax.eval_shape)
# ======================================================================

def _attn_cache(cfg, L_axis: str, L: int, B: int, S: int, window: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    Sc = min(window, S) if window else S
    return {"k": jnp.zeros((L, B, KV, Sc, hd), dtype),
            "v": jnp.zeros((L, B, KV, Sc, hd), dtype)}


def _ssm_cache(cfg, L: int, B: int, dtype):
    C = cfg.d_inner + 2 * cfg.ssm_state
    return {"conv": jnp.zeros((L, B, cfg.conv_width - 1, C), dtype),
            "ssm": jnp.zeros((L, B, cfg.ssm_heads, cfg.ssm_headdim,
                              cfg.ssm_state), dtype)}


def init_cache(cfg, B: int, cache_len: int, dtype=jnp.bfloat16):
    """Zeros cache pytree for the decoder stack (call under eval_shape for
    abstract specs).  In a hybrid pattern each slot keeps its own kind:
    conv and SSM state for ``ssm``, conv and LRU state for ``rec``, a KV
    cache for attention."""
    kinds = cfg.layer_kinds()
    L = cfg.num_layers
    if len(set(kinds)) == 1:
        kind = kinds[0]
        if kind == "ssm":
            entry = _ssm_cache(cfg, L, B, dtype)
        else:
            entry = _attn_cache(cfg, "layers", L, B, cache_len, 0, dtype)
        return {"stack": entry, "pos": jnp.zeros((B,), jnp.int32)}

    pat = cfg.block_pattern
    G = L // len(pat)
    gcache, tcache = {}, {}

    def one(kind, n):
        if kind == "ssm":
            return _ssm_cache(cfg, n, B, dtype)
        if kind == "rec":
            return {"conv": jnp.zeros((n, B, cfg.conv_width - 1, cfg.lru_width), dtype),
                    "lru": jnp.zeros((n, B, cfg.lru_width), dtype)}
        return _attn_cache(cfg, "groups", n, B, cache_len, cfg.local_window, dtype)

    for i, kind in enumerate(pat):
        gcache[f"b{i}_{kind}"] = one(kind, G)
    tail_kinds = kinds[G * len(pat):]
    for i, kind in enumerate(tail_kinds):
        e = one(kind, 1)
        tcache[f"t{i}_{kind}"] = jax.tree_util.tree_map(lambda a: a[0], e)
    return {"groups": gcache, "tail": tcache, "pos": jnp.zeros((B,), jnp.int32)}


def cache_axes(cfg, ctx) -> Any:
    """Logical axes tree matching init_cache output (for shardings)."""
    sp = ctx is not None and ctx.sp_decode
    full_attn = ("layers", "batch", None, "cache_seq" if sp else None, None)
    win_attn = ("layers", "batch", None, None, None)
    kinds = cfg.layer_kinds()
    L = cfg.num_layers
    if len(set(kinds)) == 1:
        if kinds[0] == "ssm":
            entry = {"conv": ("layers", "batch", None, None),
                     "ssm": ("layers", "batch", "ssm_heads", None, None)}
        else:
            entry = {"k": full_attn, "v": full_attn}
        return {"stack": entry, "pos": ("batch",)}
    pat = cfg.block_pattern
    G = L // len(pat)

    def one(kind, stacked=True):
        if kind == "ssm":
            c = {"conv": ("groups", "batch", None, None),
                 "ssm": ("groups", "batch", "ssm_heads", None, None)}
        elif kind == "rec":
            c = {"conv": ("groups", "batch", None, "lru"),
                 "lru": ("groups", "batch", "lru")}
        else:
            c = {"k": win_attn, "v": win_attn}
        if not stacked:
            c = jax.tree_util.tree_map(lambda ax: ax[1:], c,
                                       is_leaf=lambda x: isinstance(x, tuple))
        return c

    g = {f"b{i}_{k}": one(k) for i, k in enumerate(pat)}
    tail_kinds = kinds[G * len(pat):]
    t = {f"t{i}_{k}": one(k, stacked=False) for i, k in enumerate(tail_kinds)}
    return {"groups": g, "tail": t, "pos": ("batch",)}

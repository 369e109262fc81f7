"""Mixture-of-Experts FFN with gather-based capacity dispatch.

Design (DESIGN.md §3): instead of GShard one-hot dispatch einsums (whose
dispatch matmul is quadratic in tokens) we sort token->expert assignments,
``take`` tokens into an (E, C, d) buffer (gather: zero FLOPs), run the grouped
expert einsum (the only real FLOPs, ~= active-param FLOPs x capacity factor),
and combine with router weights.  Tokens beyond an expert's capacity are
dropped (standard practice; aux loss keeps the router balanced).
``cfg.dropless`` (granite) takes :func:`moe_dropless` instead: no
capacity, and the layer may hold a share of a wider router's experts.

Sharding: EP mode shards the E axis over "model" (qwen3: 128 experts);
expert-TP mode shards each expert's hidden dim (qwen2: 60 experts, 60 % 16 != 0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.context import shard
from repro.models.layers import P, silu, swiglu


def moe_spec(cfg):
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.d_ff
    spec = {
        "router": P((d, cfg.num_router_experts), ("embed", "experts"),
                    scale=0.02),
        "w_gate": P((E, d, ff), ("experts", "embed", "expert_mlp")),
        "w_up": P((E, d, ff), ("experts", "embed", "expert_mlp")),
        "w_down": P((E, ff, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.shared_expert_d_ff:
        sf = cfg.shared_expert_d_ff
        spec["shared"] = {
            "w_gate": P((d, sf), ("embed", "mlp")),
            "w_up": P((d, sf), ("embed", "mlp")),
            "w_down": P((sf, d), ("mlp", "embed")),
        }
        if cfg.shared_expert_gated:
            spec["shared"]["gate"] = P((d, 1), ("embed", None), scale=0.02)
    return spec


def shared_expert(sp, xt, cfg):
    """The shared expert on tokens ``xt`` (T, d): a SwiGLU, under a sigmoid
    gate where the configuration has one (qwen2-moe; granite has none)."""
    y = swiglu(xt, sp["w_gate"], sp["w_up"], sp["w_down"])
    if not cfg.shared_expert_gated:
        return y
    sgate = jax.nn.sigmoid(jnp.einsum("td,do->to", xt, sp["gate"]
                                      ).astype(jnp.float32))
    return sgate.astype(xt.dtype) * y


def _aux_loss(logits, idx, cfg):
    """Switch-style load-balance loss over the router's experts."""
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=0)
    return cfg.router_aux_coef * E * jnp.sum(me * ce)


def moe_dropless(p, x, cfg):
    """Dropless MoE over the experts this layer holds (granite).

    The router spans all ``cfg.num_router_experts`` experts: each token
    keeps its top ``experts_per_tok`` logits, and its gates are the softmax
    over those (the same as ``norm_topk_prob``).  The layer holds experts
    ``[first_expert, first_expert + num_experts)`` and computes the part of
    the result that they give: each held expert runs on every token, and
    its output is weighted by the token's gate for it, zero where the token
    did not route to it, so no routed token is dropped however uneven the
    routing.  Under expert parallelism the shares of all chips add up to
    the whole layer; the shared expert is every chip's alike.
    """
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    top, idx = jax.lax.top_k(logits, cfg.experts_per_tok)       # (T,K)
    gate = jax.nn.softmax(top, axis=-1)
    # each held expert's gate per token; routes to experts held elsewhere
    # fall outside one_hot's range and weigh nothing here
    held = jax.nn.one_hot(idx - cfg.first_expert, cfg.num_experts,
                          dtype=jnp.float32)                   # (T,K,n)
    w = jnp.einsum("tk,tkn->nt", gate, held).astype(x.dtype)
    g = jnp.einsum("td,ndf->ntf", xt, p["w_gate"])
    u = jnp.einsum("td,ndf->ntf", xt, p["w_up"])
    out = jnp.einsum("ntf,nfd->td", silu(g) * u * w[:, :, None], p["w_down"])
    if cfg.shared_expert_d_ff:
        out = out + shared_expert(p["shared"], xt, cfg)
    return out.reshape(B, S, d), _aux_loss(logits, idx[:, 0], cfg)


def _capacity(tokens: int, cfg) -> int:
    c = int(tokens * cfg.experts_per_tok * cfg.capacity_factor / cfg.num_experts)
    return max(8, (c + 7) // 8 * 8)


def moe_ffn_shardmap(p, x, cfg, ctx):
    """MoE with *local* token routing inside shard_map (DESIGN §3).

    Tokens stay on their data shard — GSPMD's global-sort collectives vanish.
    Two expert layouts:

    * **EP** (E %% model == 0, e.g. qwen3/128): each model shard owns E/m
      experts and buffers only slots routed to them; traffic = per-layer
      ff-sharded weight gather over the data axes + ONE (T_local, d) psum.
    * **expert-TP** (e.g. qwen2/60): every shard holds all experts with a
      1/m slice of the ffn dim (row-parallel); traffic = ONE (T_local, d)
      psum of the combined output (the combine is linear, so it commutes
      with the cross-shard sum).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as PS

    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_tok
    mesh = ctx.mesh
    msize = mesh.shape["model"]
    ep = ctx.rules.get("expert_mode") == "ep"
    Eloc = E // msize if ep else E
    batch = ctx.rules["batch"]
    ff_shard = ctx.rules.get("expert_mlp")  # EP: data axes; TP: "model"
    ff_axes = (() if not ep else
               ((ff_shard,) if isinstance(ff_shard, str) else tuple(ff_shard or ())))

    e_spec = "model" if ep else None
    w_spec = PS(e_spec, None, ff_shard)
    wd_spec = PS(e_spec, ff_shard, None)

    def local(xb, router, wg, wu, wd):
        Bl, Sl, _ = xb.shape
        Tl = Bl * Sl
        xt = xb.reshape(Tl, d)
        C = max(8, int(np.ceil(Tl * K * cfg.capacity_factor / E / 8)) * 8)

        logits = jnp.einsum("td,de->te", xt, router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, K)
        if cfg.norm_topk_prob:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

        e_flat = idx.reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        counts = jnp.bincount(e_flat, length=E)
        starts = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(Tl * K, dtype=jnp.int32) - starts[e_flat[order]]
        rank = jnp.zeros((Tl * K,), jnp.int32).at[order].set(rank_sorted)
        keep = (rank < C).reshape(Tl, K)

        if ep:  # only slots owned by THIS model shard get buffered
            e0 = jax.lax.axis_index("model").astype(jnp.int32) * Eloc
            local_e = idx - e0
            mine = keep & (local_e >= 0) & (local_e < Eloc)
            slot = jnp.where(mine, local_e * C + rank.reshape(Tl, K), Eloc * C)
        else:   # expert-TP: all experts local (ffn dim row-parallel)
            mine = keep
            slot = jnp.where(mine, idx * C + rank.reshape(Tl, K), Eloc * C)

        buf = jnp.zeros((Eloc * C + 1, d), x.dtype)
        for k in range(K):
            buf = buf.at[slot[:, k]].set(xt)
        buf = buf[: Eloc * C].reshape(Eloc, C, d)

        # EP: gather the ff-sharded weights of the local experts (fsdp-style)
        for ax in ff_axes:
            wg = jax.lax.all_gather(wg, ax, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, ax, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, ax, axis=1, tiled=True)

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        eo = jnp.einsum("ecf,efd->ecd", silu(g) * u, wd)

        eo_flat = jnp.concatenate(
            [eo.reshape(Eloc * C, d), jnp.zeros((1, d), eo.dtype)], axis=0)
        w = (gate * mine).astype(x.dtype)
        out = jnp.zeros((Tl, d), x.dtype)
        for k in range(K):
            out = out + eo_flat[slot[:, k]] * w[:, k:k + 1]
        # EP: sum expert-shard partials; TP: sum row-parallel ffn partials —
        # either way exactly one (T_local, d) psum over the model axis.
        out = jax.lax.psum(out, "model")
        return out.reshape(Bl, Sl, d)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(batch, None, None), PS(None, None), w_spec, w_spec, wd_spec),
        out_specs=PS(batch, None, None),
        check_vma=False,
    )
    out = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    # aux load-balance loss on the global routing (router matmul is tiny)
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).reshape(-1, E)
    idx = jnp.argmax(probs, axis=-1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    if cfg.shared_expert_d_ff:
        out = out + shared_expert(p["shared"], x.reshape(-1, d),
                                  cfg).reshape(B, S, d)
    return out, aux


def moe_ffn(p, x, cfg, ctx=None):
    """x: (B, S, d) -> (out, aux_loss)."""
    if (ctx is not None and ctx.extra.get("moe_impl") == "shardmap"
            and ctx.rules.get("expert_mode") in ("ep", "tp")):
        return moe_ffn_shardmap(p, x, cfg, ctx)
    if cfg.dropless:
        return moe_dropless(p, x, cfg)
    B, S, d = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.experts_per_tok
    C = _capacity(T, cfg)
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                       # (T,K)
    if cfg.norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    aux = _aux_loss(logits, idx[:, 0], cfg)

    # ---- dispatch: rank each (token, slot) within its expert via sort ----
    e_flat = idx.reshape(-1)                                  # (T*K,)
    order = jnp.argsort(e_flat, stable=True)                  # group by expert
    counts = jnp.bincount(e_flat, length=E)                   # (E,)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(T * K, dtype=jnp.int32) - starts[e_flat[order]]
    rank = jnp.zeros((T * K,), jnp.int32).at[order].set(rank_sorted)
    keep = (rank < C).reshape(T, K)
    slot = jnp.where(keep, idx * C + rank.reshape(T, K), E * C)  # drop->scratch

    # K scatters of (T, d) — never materializes a (T*K, d) intermediate
    buf = jnp.zeros((E * C + 1, d), x.dtype)
    for k in range(K):
        buf = buf.at[slot[:, k]].set(xt)
    buf = buf[: E * C].reshape(E, C, d)
    buf = shard(ctx, buf, "experts", "batch", None)

    # ---- grouped expert SwiGLU (the real FLOPs) ----
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = shard(ctx, silu(g) * u, "experts", "batch", "expert_mlp")
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    eo = shard(ctx, eo, "experts", "batch", None)

    # ---- combine: K gathers of (T, d), weighted sum ----
    eo_flat = jnp.concatenate([eo.reshape(E * C, d),
                               jnp.zeros((1, d), eo.dtype)], axis=0)
    w = (gate * keep).astype(x.dtype)                         # (T,K)
    out = jnp.zeros((T, d), x.dtype)
    for k in range(K):
        out = out + eo_flat[slot[:, k]] * w[:, k:k + 1]

    if cfg.shared_expert_d_ff:
        out = out + shared_expert(p["shared"], xt, cfg)
    return out.reshape(B, S, d), aux

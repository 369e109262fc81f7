"""Model facade: one API over every assigned architecture family.

    lm = LM(cfg, max_seq=4096)
    params = lm.init(key)                          # real (smoke tests)
    aparams = lm.abstract()                        # ShapeDtypeStruct (lowering)
    loss, metrics = lm.loss(params, batch, ctx)
    logits, cache = lm.prefill(params, batch, ctx)
    logits, cache = lm.decode_step(params, cache, batch, ctx)
"""
from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.context import DistContext, shard
from repro.models import encdec, transformer
from repro.models.layers import (
    P, abstract_params, axes_tree, embed_lookup, embed_spec, init_params,
    logits_from_embed, rms_norm, softmax_xent,
)


class LM:
    def __init__(self, cfg: ModelConfig, max_seq: int = 4096):
        self.cfg = cfg
        self.max_seq = max_seq
        self._spec = self._build_spec()

    # ------------------------------------------------------------------
    def _build_spec(self):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec.encdec_spec(cfg, self.max_seq)
        spec: dict[str, Any] = {
            "embed": embed_spec(cfg),
            "decoder": transformer.decoder_spec(cfg),
            "ln_f": P((cfg.d_model,), ("embed",), init="zeros"),
        }
        if not cfg.tie_embeddings:
            spec["w_out"] = P((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"))
        return spec

    def spec(self):
        return self._spec

    def init(self, key, dtype=jnp.bfloat16):
        return init_params(self._spec, key, dtype)

    def abstract(self, dtype=jnp.bfloat16):
        return abstract_params(self._spec, dtype)

    def axes(self):
        return axes_tree(self._spec)

    # ------------------------------------------------------------------
    def _embed_in(self, params, tokens, ctx):
        x = embed_lookup(params["embed"], tokens)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * self.cfg.embedding_multiplier
        return shard(ctx, x, "batch", "seq", None)

    def _logits(self, params, x, ctx):
        table = params["embed"] if self.cfg.tie_embeddings else params["w_out"]
        out = logits_from_embed(x, table)
        if self.cfg.logits_scaling != 1.0:
            out = out / self.cfg.logits_scaling
        return shard(ctx, out, "batch", "seq", "vocab")

    # ------------------------------------------------------------------
    def forward(self, params, batch, ctx: DistContext | None = None, *,
                remat: str = "none", want_cache: bool = False,
                cache_len: int | None = None):
        """Teacher-forced forward over full sequences.

        Returns (logits, aux, cache_or_None). ``batch["tokens"]`` is the
        decoder input (B, S); extra modality inputs per family.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape

        if cfg.family == "encdec":
            enc_out = encdec.encoder_forward(params, batch["encoder_frames"], cfg, ctx)
            x = self._embed_in(params, tokens, ctx)
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            x = x + params["dec_pos"][:S][None].astype(x.dtype)
            x, cache = encdec.decoder_forward(params, x, enc_out, cfg, ctx,
                                              positions, want_cache=want_cache,
                                              cache_len=cache_len)
            x = rms_norm(x, params["ln_f"], cfg.norm_eps)
            if want_cache:
                cache["pos"] = jnp.full((B,), S, jnp.int32)
            return self._logits(params, x, ctx), 0.0, cache

        x = self._embed_in(params, tokens, ctx)
        if cfg.family == "vlm":
            ve = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([ve, x], axis=1)
        S_tot = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S_tot)[None, :], (B, S_tot))
        x, aux, cache = transformer.decoder_forward(
            params["decoder"], x, cfg, ctx, positions, remat=remat,
            want_cache=want_cache, cache_len=cache_len)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        if want_cache:
            cache["pos"] = jnp.full((B,), S_tot, jnp.int32)
        return self._logits(params, x, ctx), aux, cache

    # ------------------------------------------------------------------
    def loss(self, params, batch, ctx: DistContext | None = None, *,
             remat: str = "none"):
        """batch["tokens"]: (B, S+1) -> next-token CE (+ MoE aux)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        inp, labels = tokens[:, :-1], tokens[:, 1:]
        fwd_batch = dict(batch, tokens=inp)
        logits, aux, _ = self.forward(params, fwd_batch, ctx, remat=remat)
        if cfg.family == "vlm":
            logits = logits[:, cfg.num_patches:, :]
        ce = softmax_xent(logits, labels, cfg.vocab_size)
        metrics = {"ce": ce, "aux": aux}
        return ce + aux, metrics

    # ------------------------------------------------------------------
    def prefill(self, params, batch, ctx: DistContext | None = None,
                cache_len: int | None = None):
        """Process a prompt; returns (last-position logits (B,V), cache)."""
        logits, _, cache = self.forward(params, batch, ctx, want_cache=True,
                                        cache_len=cache_len)
        return logits[:, -1, :], cache

    def decode_step(self, params, cache, batch, ctx: DistContext | None = None):
        """One new token. batch["token"]: (B,1). Returns (logits (B,V), cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed_in(params, batch["token"], ctx)
        if cfg.family == "encdec":
            x = x + jnp.take(params["dec_pos"], jnp.clip(pos, 0, self.max_seq - 1),
                             axis=0)[:, None, :].astype(x.dtype)
            x, new_cache = encdec.decoder_decode(params, x, cfg, ctx, pos, cache)
        else:
            x, new_cache = transformer.decoder_decode(
                params["decoder"], x, cfg, ctx, pos, cache)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._logits(params, x, ctx)[:, 0, :]
        new_cache["pos"] = pos + 1
        return logits, new_cache

    # ------------------------------------------------------------------
    def init_cache(self, B: int, cache_len: int, dtype=jnp.bfloat16):
        if self.cfg.family == "encdec":
            return encdec.init_cache(self.cfg, B, cache_len, dtype)
        return transformer.init_cache(self.cfg, B, cache_len, dtype)

    def cache_axes(self, ctx: DistContext | None = None):
        if self.cfg.family == "encdec":
            return encdec.cache_axes(self.cfg, ctx)
        return transformer.cache_axes(self.cfg, ctx)

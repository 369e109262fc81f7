"""pjit-able train / prefill / decode steps with full sharding trees.

``build_train_step`` / ``build_prefill_step`` / ``build_decode_step`` return
(jitted_fn, in_shardings, out_shardings, abstract_inputs) ready for
``.lower(...).compile()``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.distributed import sharding as shd
from repro.distributed.context import DistContext
from repro.models.model import LM
from repro.optim.optimizer import OptState, adamw_update, init_opt_state


# ----------------------------------------------------------------------
# abstract inputs (ShapeDtypeStruct stand-ins; no allocation)
# ----------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig, lm: LM | None = None,
                dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStructs for every model input of this (arch x shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        n_text = S - (cfg.num_patches if cfg.family == "vlm" else 0)
        out = {"tokens": sds((B, n_text + 1), jnp.int32)}
    elif shape.kind == "prefill":
        n_text = S - (cfg.num_patches if cfg.family == "vlm" else 0)
        out = {"tokens": sds((B, n_text), jnp.int32)}
    else:  # decode
        out = {"token": sds((B, 1), jnp.int32)}
    if shape.kind in ("train", "prefill"):
        if cfg.family == "vlm":
            out["vision_embeds"] = sds((B, cfg.num_patches, cfg.d_model), dtype)
        if cfg.family == "encdec":
            out["encoder_frames"] = sds((B, cfg.encoder_seq, cfg.d_model), dtype)
    return out


def cache_specs(lm: LM, B: int, cache_len: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: lm.init_cache(B, cache_len, dtype))


# ----------------------------------------------------------------------
# train step
# ----------------------------------------------------------------------

def build_train_step(lm: LM, tc: TrainConfig, ctx: DistContext,
                     shape: ShapeConfig):
    cfg = lm.cfg
    n_micro = max(tc.microbatches, 1)

    def loss_fn(params, batch):
        return lm.loss(params, batch, ctx, remat=tc.remat)

    def train_step(params, opt: OptState, batch):
        if n_micro > 1:
            def resh(x):
                return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
            mb = jax.tree_util.tree_map(resh, batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def micro(carry, b):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
                gsum = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32), gsum, g)
                return (gsum, lsum + l), None

            (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), mb)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro
        else:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
        opt2, params2, om = adamw_update(tc, opt, grads, params)
        return params2, opt2, {"loss": loss, **om}

    aparams = lm.abstract()
    axes = lm.axes()
    p_sh = shd.params_shardings(ctx, axes, aparams)
    o_sh = shd.opt_shardings(ctx, axes, aparams)
    binputs = input_specs(cfg, shape, lm)
    b_sh = shd.batch_shardings(ctx, binputs, shape.global_batch)
    rep = NamedSharding(ctx.mesh, PS())
    metrics_sh = {"loss": rep, "lr": rep, "grad_norm": rep}

    jf = jax.jit(train_step,
                 in_shardings=(p_sh, o_sh, b_sh),
                 out_shardings=(p_sh, o_sh, metrics_sh),
                 donate_argnums=(0, 1))
    aopt = jax.eval_shape(init_opt_state, aparams)
    return jf, (aparams, aopt, binputs)


# ----------------------------------------------------------------------
# serve steps
# ----------------------------------------------------------------------

def build_prefill_step(lm: LM, ctx: DistContext, shape: ShapeConfig,
                       cache_len: int | None = None):
    cfg = lm.cfg
    cache_len = cache_len or shape.seq_len

    def prefill(params, batch):
        return lm.prefill(params, batch, ctx, cache_len=cache_len)

    aparams = lm.abstract()
    p_sh = shd.params_shardings(ctx, lm.axes(), aparams)
    binputs = input_specs(cfg, shape, lm)
    b_sh = shd.batch_shardings(ctx, binputs, shape.global_batch)
    acache = cache_specs(lm, shape.global_batch, cache_len)
    c_sh = shd.cache_shardings(ctx, lm.cache_axes(ctx), acache,
                               shape.global_batch)
    bspec = shd.batch_pspec(ctx, shape.global_batch)
    logits_sh = NamedSharding(ctx.mesh, PS(bspec, ctx.rules.get("vocab")))

    jf = jax.jit(prefill, in_shardings=(p_sh, b_sh),
                 out_shardings=(logits_sh, c_sh))
    return jf, (aparams, binputs)


def build_decode_step(lm: LM, ctx: DistContext, shape: ShapeConfig):
    cfg = lm.cfg

    def decode(params, cache, batch):
        return lm.decode_step(params, cache, batch, ctx)

    aparams = lm.abstract()
    p_sh = shd.params_shardings(ctx, lm.axes(), aparams)
    acache = cache_specs(lm, shape.global_batch, shape.seq_len)
    c_sh = shd.cache_shardings(ctx, lm.cache_axes(ctx), acache,
                               shape.global_batch)
    binputs = input_specs(cfg, shape, lm)
    b_sh = shd.batch_shardings(ctx, binputs, shape.global_batch)
    bspec = shd.batch_pspec(ctx, shape.global_batch)
    logits_sh = NamedSharding(ctx.mesh, PS(bspec, ctx.rules.get("vocab")))

    jf = jax.jit(decode, in_shardings=(p_sh, c_sh, b_sh),
                 out_shardings=(logits_sh, c_sh), donate_argnums=(1,))
    return jf, (aparams, acache, binputs)

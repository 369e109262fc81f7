"""Per-arch reduced-config smoke tests: one real train step on CPU with
shape + finiteness asserts."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, TrainConfig, get_config
from repro.data import TokenPipeline
from repro.configs.base import ShapeConfig
from repro.models import LM
from repro.optim import adamw_update, init_opt_state

B, S = 2, 32


def _batch(cfg, seed=0):
    pipe = TokenPipeline(cfg, ShapeConfig("smoke", "train", S, B), seed=seed)
    hb = pipe.train_batch(0)
    return {k: jnp.asarray(v) for k, v in hb.items()}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_smoke(arch):
    cfg = get_config(arch, reduced=True)
    lm = LM(cfg, max_seq=S + 1)
    params = lm.init(jax.random.PRNGKey(0))
    tc = TrainConfig(total_steps=10, warmup_steps=2, schedule=cfg.schedule)
    opt = init_opt_state(params)
    batch = _batch(cfg)

    @jax.jit
    def step(params, opt, batch):
        (loss, m), grads = jax.value_and_grad(lm.loss, has_aux=True)(params, batch)
        opt2, params2, om = adamw_update(tc, opt, grads, params)
        return params2, opt2, loss, om["grad_norm"]

    p2, o2, loss, gnorm = step(params, opt, batch)
    assert jnp.isfinite(loss), arch
    assert jnp.isfinite(gnorm) and float(gnorm) > 0, arch
    # params changed, structure/shapes preserved
    same = jax.tree_util.tree_map(lambda a, b: a.shape == b.shape, params, p2)
    assert all(jax.tree_util.tree_leaves(same))
    changed = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), params, p2)
    assert any(jax.tree_util.tree_leaves(changed)), arch
    # loss near ln(vocab) at random init
    assert 0.5 * jnp.log(cfg.vocab_size) < loss < 2.0 * jnp.log(cfg.vocab_size)


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-370m", "recurrentgemma-9b",
                                  "whisper-tiny", "qwen3-moe-235b-a22b",
                                  "internvl2-2b"])
def test_forward_output_shape(arch):
    cfg = get_config(arch, reduced=True)
    lm = LM(cfg, max_seq=S)
    params = lm.init(jax.random.PRNGKey(1))
    batch = _batch(cfg)
    batch["tokens"] = batch["tokens"][:, :S]
    logits, aux, _ = lm.forward(params, batch)
    S_tot = S + (cfg.num_patches if cfg.family == "vlm" else 0)
    assert logits.shape == (B, S_tot, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

"""Notebook cells of the mamba2_370m_train configuration.

The notebook is the user's code: a fine-tuning session that keeps
mamba2-370m's weights and AdamW state on the chip.  ``setup`` builds the
model from the configuration file and makes the weights and optimizer state
on the device in one jitted call from the seed; ``train`` takes one step on
a seeded batch; ``eval`` computes the loss on a seeded held-out batch and
leaves the weights as they are; ``inspect`` reads the losses back.
"""
from __future__ import annotations

OUTPUTS = ("losses", "eval_losses")


def cells(config: dict, traffic: dict, keys: dict) -> dict[str, str]:
    """Cell sources by name; ``keys`` holds the 31-bit seeds the harness
    derived from ``--seed``."""
    seq = int(traffic["seq"])
    batch = int(traffic["batch"])
    opt = config["optimizer"]
    setup = f"""
import functools
import jax
import jax.numpy as jnp
from repro.configs.base import ModelConfig, TrainConfig
from repro.models import LM
from repro.optim import adamw_update, init_opt_state

cfg = ModelConfig(**{dict(config["model"])!r})
lm = LM(cfg, max_seq={seq + 1})
tc = TrainConfig(learning_rate={float(opt["learning_rate"])!r},
                 warmup_steps={int(opt["warmup_steps"])},
                 total_steps={int(opt["total_steps"])},
                 remat={traffic["remat"]!r})


def init_state(key):
    p = lm.init(key, jnp.{config["param_dtype"]})
    return p, init_opt_state(p)


params, opt = jax.jit(init_state)(jax.random.PRNGKey({keys["weights"]}))
data_key = jax.random.PRNGKey({keys["data"]})
held_out = 1 << 30
step = 0
evals = 0
losses = []
eval_losses = []


def next_batch(i):
    return jax.random.randint(jax.random.fold_in(data_key, i),
                              ({batch}, {seq + 1}), 0, cfg.vocab_size,
                              dtype=jnp.int32)


def train_step(params, opt, toks):
    (loss, _), grads = jax.value_and_grad(
        functools.partial(lm.loss, remat=tc.remat), has_aux=True)(
        params, {{"tokens": toks}})
    opt, params, _ = adamw_update(tc, opt, grads, params)
    return params, opt, loss


def eval_step(params, toks):
    return lm.loss(params, {{"tokens": toks}})[0]
"""
    train = """
toks = jax.jit(next_batch)(step)
params, opt, loss = jax.jit(train_step, donate_argnums=(0, 1))(
    params, opt, toks)
step += 1
losses.append(float(loss))
"""
    evaluate = """
toks_eval = jax.jit(next_batch)(held_out + evals)
eval_loss = jax.jit(eval_step)(params, toks_eval)
evals += 1
eval_losses.append(float(eval_loss))
"""
    inspect = """
last = (step, evals, losses[-1:], eval_losses[-1:])
"""
    return {"setup": setup, "train": train, "eval": evaluate,
            "inspect": inspect}

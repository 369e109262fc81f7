"""Notebook cells of the granite_4_0_h_small_generate configuration.

The notebook is the user's code: a batched long-document generation
session with Granite 4.0-H Small, whose weights and two-kind cache (a KV
cache for the attention layer, conv and SSM state for the Mamba-2 layers)
live on the chip.  ``setup`` builds the model from the configuration file
and makes bfloat16 weights on the device in one jitted call from the seed;
``prefill`` runs seeded prompts through ``LM.prefill`` in groups small
enough to fit and joins the groups' caches along the batch axis;
``generate`` decodes greedily for every sequence in one jitted call that
donates the cache and writes the new tokens into ``generated``, a fixed
buffer of one slot per free cache slot; ``inspect`` reads the last tokens
back.  ``verify`` holds the timed path against the plain float32
reference (``granite_ref.py``) and raises past a tolerance: the prefill's
own last logits, and the logits of the jitted, donating function that
``generate`` calls, at the timed batch, on a copy of the whole cache, for
as many calls as set-up's warm-up and a window make.  Greedy decoding is
deterministic, so those calls give the tokens that ``generate`` gives
later from the same cache.
"""
from __future__ import annotations

OUTPUTS = ("generated", "verify_errors")


def cells(config: dict, traffic: dict, keys: dict) -> dict[str, str]:
    """Cell sources by name; ``keys`` holds the 31-bit seeds the harness
    derived from ``--seed``.  A model library that cannot build this
    configuration fails here, before the session starts."""
    from repro.configs.base import ModelConfig
    model = dict(config["model"])
    ModelConfig(**dict(model, block_pattern=tuple(model["block_pattern"])))
    batch = int(traffic["batch"])
    prompt = int(traffic["prompt"])
    cache_len = int(traffic["cache_len"])
    group = int(traffic["prefill_group"])
    n_new = int(traffic["decode_tokens"])
    check = config["verify"]
    calls = int(check["decode_calls"])
    if calls * n_new > cache_len - prompt:
        raise ValueError(f"verify decodes {calls * n_new} tokens, the cache "
                         f"has {cache_len - prompt} free slots")
    rows = [round(i * (batch - 1) / max(int(check["sequences"]) - 1, 1))
            for i in range(int(check["sequences"]))]
    setup = f"""
import jax
import jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.models import LM

model = {model!r}
cfg = ModelConfig(**dict(model, block_pattern=tuple(model["block_pattern"])))
lm = LM(cfg, max_seq={cache_len})
params = jax.jit(lambda key: lm.init(key, jnp.{config["param_dtype"]}))(
    jax.random.PRNGKey({keys["weights"]}))


# {n_new} greedy steps: the cache, the last ids, generated with the new
# tokens at at, and each step's logits (step, batch, vocabulary)
def decode_tokens(params, cache, ids, generated, at):
    def step(carry, _):
        cache, ids = carry
        logits, cache = lm.decode_step(params, cache, {{"token": ids[:, None]}})
        ids = jnp.argmax(logits, -1).astype(jnp.int32)
        return (cache, ids), (ids, logits)

    (cache, ids), (out, logits) = jax.lax.scan(step, (cache, ids), None,
                                               length={n_new})
    return cache, ids, jax.lax.dynamic_update_slice_in_dim(
        generated, out.T, at, axis=1), logits
"""
    prefill = f"""
def _prefill():
    prompts = jax.random.randint(jax.random.PRNGKey({keys["data"]}),
                                 ({batch}, {prompt}), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    step = jax.jit(lambda p, t: lm.prefill(p, {{"tokens": t}},
                                           cache_len={cache_len}))
    parts = [step(params, prompts[i:i + {group}])
             for i in range(0, {batch}, {group})]
    cache = jax.tree_util.tree_map(
        lambda ax, *xs: jnp.concatenate(xs, axis=ax.index("batch")),
        lm.cache_axes(), *[c for _, c in parts],
        is_leaf=lambda t: isinstance(t, tuple))
    return prompts, jnp.concatenate([l for l, _ in parts]), cache


prompts, last_logits, cache = _prefill()
del _prefill
next_ids = jnp.argmax(last_logits, -1).astype(jnp.int32)
generated = jnp.zeros(({batch}, {cache_len - prompt}), jnp.int32)
filled = {prompt}
"""
    verify = f"""
def _verify():
    import dataclasses
    import time

    import granite_ref

    rows = jnp.array({rows})
    kept = jnp.{check["cache_dtype"]}
    # a copy of the whole cache, for the donating call to consume
    c = jax.tree_util.tree_map(lambda a: jnp.copy(
        a.astype(kept).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a), cache)
    step = jax.jit(decode_tokens, donate_argnums=(1, 3))
    t = time.perf_counter()
    ids, g = next_ids, jnp.zeros_like(generated)
    logits = [last_logits[rows][:, None]]
    for i in range({calls}):
        c, ids, g, out = step(params, c, ids, g, i * {n_new})
        logits.append(jnp.swapaxes(out[:, rows], 0, 1))
    got = jnp.concatenate(logits, 1)
    toks = jnp.concatenate([prompts[rows], next_ids[rows][:, None],
                            g[rows, :{calls * n_new - 1}]], 1)
    del c, ids, g, out, logits
    jax.block_until_ready(got)
    program_s = time.perf_counter() - t
    t = time.perf_counter()
    ref = granite_ref.forward(params, toks, dataclasses.asdict(cfg),
                              last={calls * n_new + 1},
                              weights=jnp.{check["reference_weights"]})
    err = jax.block_until_ready(granite_ref.rel_err(got, ref))
    errors = {{"prefill": float(err[:, 0].max()),
              "decode": float(err[:, 1:].max())}}
    print("verify: logits against the reference", errors,
          {{"program_s": program_s, "reference_s": time.perf_counter() - t}})
    tolerance = {dict(check["tolerance"])!r}
    over = {{k: v for k, v in errors.items() if not v <= tolerance[k]}}
    if over:
        raise AssertionError(f"logits against the reference: {{over}}, "
                             f"tolerance {{tolerance}}")
    return errors


verify_errors = _verify()
del _verify
"""
    generate = f"""
if filled + {n_new} > {cache_len}:
    raise RuntimeError(f"{{{cache_len} - filled}} cache slots left, "
                       f"generate needs {n_new}")
cache, next_ids, generated = jax.jit(decode_tokens, donate_argnums=(1, 3))(
    params, cache, next_ids, generated, filled - {prompt})[:3]
filled += {n_new}
"""
    inspect = f"""
last = jax.lax.dynamic_slice_in_dim(generated, filled - {prompt} - 4, 4,
                                    axis=1).tolist()
"""
    return {"setup": setup, "prefill": prefill, "verify": verify,
            "generate": generate, "inspect": inspect}

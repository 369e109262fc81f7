"""granite-4.0-h-small in the model library against the plain reference
(``granite_reference.py``, the copy of ``benchmarks/chip/granite_ref.py``
kept for the CPU tests), at a small size on seeded random weights."""
import dataclasses
import json
import os

import granite_reference as ref
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import period_pattern
from repro.configs.granite_4_0_h_small import CONFIG, LAYER_TYPES
from repro.models import LM, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on both sides, so only the order of the sums differs (the chunked
# SSD scan against the token-by-token recurrence, a gate applied before or
# after the down projection): about 1e-6 of the logits' norm
TOL = 1e-4
CFG = get_config("granite-4.0-h-small", reduced=True)
PROMPT, STEPS = 40, 8


def _params(cfg=CFG, seed=0):
    return LM(cfg, max_seq=64).init(jax.random.PRNGKey(seed), jnp.float32)


def _tokens(n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (2, n), 0,
                              CFG.vocab_size)


def _ref_cfg(cfg=CFG):
    return dataclasses.asdict(cfg)


def _decode(cfg, params, toks, cache_dtype=None):
    """Prefill ``toks`` then STEPS greedy decode steps: the logits at each
    of the STEPS + 1 positions, and every token fed."""
    lm = LM(cfg, max_seq=64)
    last, cache = lm.prefill(params, {"tokens": toks}, cache_len=64)
    if cache_dtype is not None:     # the cache kept in a narrower dtype
        cache = jax.tree_util.tree_map(
            lambda a: a.astype(cache_dtype).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, cache)
    logits, fed = [last], []
    for _ in range(STEPS):
        fed.append(jnp.argmax(logits[-1], -1))
        out, cache = lm.decode_step(params, cache, {"token": fed[-1][:, None]})
        logits.append(out)
    return jnp.stack(logits, 1), jnp.concatenate([toks, jnp.stack(fed, 1)], 1)


def _decode_err(cfg, params, ref_params=None, **kw):
    logits, toks = _decode(cfg, params, _tokens(PROMPT), **kw)
    expect = ref.forward(params if ref_params is None else ref_params, toks,
                         _ref_cfg(), last=STEPS + 1)
    return float(ref.rel_err(logits, expect).max())


def test_forward_matches_reference():
    params = _params()
    toks = _tokens(48)
    logits, _, _ = LM(CFG, max_seq=64).forward(params, {"tokens": toks})
    err = ref.rel_err(logits, ref.forward(params, toks, _ref_cfg()))
    assert float(err.max()) < TOL


def test_prefill_then_decode_matches_reference():
    assert _decode_err(CFG, _params()) < TOL


def _share(p, cfg, first, n):
    """The experts [first, first + n) of a whole layer's MoE parameters."""
    cut = {k: p[k][first:first + n] for k in ("w_gate", "w_up", "w_down")}
    return dict(p, **cut), dataclasses.replace(
        cfg, num_experts=n, router_experts=cfg.num_experts,
        first_expert=first)


def _layer_moe(params):
    return params["decoder"]["groups"]["b0_ssm"]["ffn"]


def test_expert_shares_add_up_to_the_whole_layer():
    p = jax.tree_util.tree_map(lambda a: a[0], _layer_moe(_params()))
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, CFG.d_model))
    n = 2                                      # 8 shares of 16 experts
    shared = moe.shared_expert(p["shared"], h.reshape(-1, CFG.d_model), CFG)
    # every share computes the shared expert; count it once
    total = -(CFG.num_experts // n - 1) * shared.reshape(h.shape)
    for first in range(0, CFG.num_experts, n):
        held, cfg = _share(p, CFG, first, n)
        total = total + moe.moe_dropless(held, h, cfg)[0]
    whole = ref.moe(p, h, _ref_cfg())
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_every_token_routed_to_one_expert_drops_nothing():
    p = jax.tree_util.tree_map(lambda a: a[0], _layer_moe(_params()))
    # every token's largest router logit is expert 3's
    p = dict(p, router=p["router"].at[:, 3].set(1.0))
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 32, CFG.d_model)) + 4.0
    assert bool(jnp.all(jnp.argmax(h.reshape(-1, CFG.d_model) @ p["router"],
                                   -1) == 3))
    # the share that holds expert 3 alone gives every token its part
    held, cfg3 = _share(p, CFG, 3, 1)
    shared = moe.shared_expert(p["shared"], h.reshape(-1, CFG.d_model), CFG)
    part = moe.moe_dropless(held, h, cfg3)[0].reshape(-1, CFG.d_model) - shared
    expect = ref.moe(held, h, _ref_cfg(cfg3)).reshape(-1, CFG.d_model) - shared
    np.testing.assert_allclose(part, expect, rtol=1e-5, atol=1e-5)
    assert bool(jnp.all(jnp.abs(part).max(-1) > 0))
    # the whole layer agrees too, where a capacity would have dropped
    whole = moe.moe_dropless(p, h, CFG)[0]
    np.testing.assert_allclose(whole, ref.moe(p, h, _ref_cfg()), rtol=1e-5,
                               atol=1e-5)
    capped = moe.moe_ffn(p, h, dataclasses.replace(CFG, dropless=False))[0]
    assert not np.allclose(capped, whole, rtol=1e-3, atol=1e-3)


def _without(params, what):
    """``params`` with the shared MLP, or held expert 0, left out of every
    layer."""
    def cut(path, a):
        keys = [getattr(k, "key", None) for k in path]
        if keys[-1] != "w_down" or "ffn" not in keys:
            return a
        if what == "shared" and "shared" in keys:
            return jnp.zeros_like(a)
        if what == "expert" and "shared" not in keys:
            return a.at[:, 0].set(0.0)
        return a
    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.mark.parametrize("control", ["residual_multiplier", "shared_mlp",
                                     "held_expert", "float8_cache"])
def test_controls_fail_the_comparison(control):
    params = _params()
    if control == "residual_multiplier":
        err = _decode_err(dataclasses.replace(CFG, residual_multiplier=1.0),
                          params)
    elif control == "shared_mlp":
        err = _decode_err(CFG, _without(params, "shared"), ref_params=params)
    elif control == "held_expert":
        err = _decode_err(CFG, _without(params, "expert"), ref_params=params)
    else:
        err = _decode_err(CFG, params, cache_dtype=jnp.float8_e4m3fn)
    assert err > 10 * TOL


def test_reference_copies_are_the_same():
    with open(os.path.join(ROOT, "tests", "granite_reference.py")) as a, \
            open(os.path.join(ROOT, "benchmarks", "chip", "granite_ref.py")) as b:
        assert a.read() == b.read()


def test_pattern_is_the_period_of_the_published_layer_types():
    assert len(LAYER_TYPES) == CONFIG.num_layers
    assert CONFIG.block_pattern == ("ssm",) * 5 + ("attn",) + ("ssm",) * 4
    kinds = CONFIG.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]


def test_benchmark_configuration_is_the_registry_share():
    """The chip cell's model is the registry's at one period, 9 of 72
    experts and 1/8 of the vocabulary, every width as published."""
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           "granite_4_0_h_small_generate.json")) as f:
        file = json.load(f)
    model = dict(file["model"], block_pattern=tuple(
        file["model"]["block_pattern"]))
    assert model["block_pattern"] == period_pattern(file["layer_types"])
    share = dataclasses.replace(
        CONFIG, num_layers=len(model["block_pattern"]), num_experts=9,
        router_experts=72, vocab_size=CONFIG.vocab_size // 8)
    assert dataclasses.replace(share, **model) == share
    assert (file["num_hidden_layers"], file["num_local_experts"],
            file["vocab_size"]) == (10, 9, 12544)

"""Model-level correctness: decode-vs-forward equivalence, local attention,
RoPE, MoE determinism."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import LM
from repro.models.attention import (
    banded_local_attention, full_causal_attention,
)

KEY = jax.random.PRNGKey(3)


def _decode_consistency(arch, **cfg_over):
    cfg = get_config(arch, reduced=True)
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)
    B, S = 2, 64
    lm = LM(cfg, max_seq=128)
    params = lm.init(KEY, dtype=jnp.float32)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    cache_len = S
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.full((B, cfg.num_patches, cfg.d_model),
                                          0.01, jnp.float32)
        cache_len = S + cfg.num_patches
    if cfg.family == "encdec":
        batch["encoder_frames"] = jnp.full((B, cfg.encoder_seq, cfg.d_model),
                                           0.01, jnp.float32)
    full, _, _ = lm.forward(params, batch)
    _, cache = lm.prefill(params, dict(batch, tokens=toks[:, :S - 1]),
                          cache_len=cache_len)
    dec, _ = lm.decode_step(params, cache, {"token": toks[:, S - 1:S]})
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("arch", ["yi-6b", "stablelm-12b", "mamba2-370m",
                                  "recurrentgemma-9b", "whisper-tiny",
                                  "internvl2-2b", "qwen3-moe-235b-a22b"])
def test_decode_matches_forward(arch):
    # MoE needs headroom so capacity drops are identical across paths
    over = {"capacity_factor": 8.0} if "moe" in arch else {}
    _decode_consistency(arch, **over)


def test_banded_equals_masked_full():
    B, S, H, KV, hd, W = 1, 128, 4, 2, 16, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    banded = banded_local_attention(q, k, v, window=W)
    # reference: full attention with an explicit window mask
    qg = q.reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k) * hd ** -0.5
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = (i >= j) & (i - j < W)
    s = jnp.where(mask[None, None, None], s, -1e30)
    ref = jnp.einsum("bkgqs,bskh->bqkgh",
                     jax.nn.softmax(s, -1), v).reshape(B, S, H, hd)
    np.testing.assert_allclose(np.asarray(banded), np.asarray(ref), atol=1e-5)


def test_chunked_causal_equals_unchunked():
    B, S, H, hd = 1, 128, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    a = full_causal_attention(q, k, v, chunk_q=32)
    b = full_causal_attention(q, k, v, chunk_q=S)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_rope_relative_shift_invariance():
    from repro.models.layers import apply_rope
    hd, S = 32, 16
    x = jax.random.normal(KEY, (1, S, 2, hd), jnp.float32)
    p0 = jnp.arange(S)[None, :]
    r0 = apply_rope(x, p0)
    r7 = apply_rope(x, p0 + 7)
    # inner products between same relative offsets are preserved
    d0 = jnp.einsum("bshd,bthd->bhst", r0, r0)
    d7 = jnp.einsum("bshd,bthd->bhst", r7, r7)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d7), atol=1e-3)


def test_partial_rope_only_rotates_prefix():
    from repro.models.layers import apply_rope
    hd = 32
    x = jnp.ones((1, 4, hd), jnp.float32)
    out = apply_rope(x, jnp.arange(4)[None, :], rope_pct=0.25)
    rot = int(hd * 0.25)
    np.testing.assert_array_equal(np.asarray(out[..., rot:]),
                                  np.asarray(x[..., rot:]))
    assert not np.allclose(np.asarray(out[0, 1:, :rot]),
                           np.asarray(x[0, 1:, :rot]))


def test_moe_determinism_and_aux():
    from repro.models.moe import moe_ffn
    from repro.models.layers import init_params
    from repro.models.moe import moe_spec
    cfg = get_config("qwen3-moe-235b-a22b", reduced=True)
    p = init_params(moe_spec(cfg), KEY, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    y1, a1 = moe_ffn(p, x, cfg)
    y2, a2 = moe_ffn(p, x, cfg)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert float(a1) >= 0 and jnp.isfinite(a1)


def test_vocab_padding_never_predicted():
    cfg = get_config("minicpm-2b", reduced=True)  # odd vocab 503 -> padded 512
    lm = LM(cfg, max_seq=16)
    params = lm.init(KEY)
    toks = jax.random.randint(KEY, (1, 16), 0, cfg.vocab_size)
    loss, _ = lm.loss(params, {"tokens": toks})
    assert jnp.isfinite(loss)
    assert cfg.padded_vocab == 512 and cfg.vocab_size == 503


def test_ssd_gradients_finite_at_full_chunk_length():
    """At a real chunk length the decays above the diagonal overflow; the
    intra-chunk mask must keep them out of the backward pass."""
    from repro.models.ssm import ssd_chunked
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    B, S, H, Pd, N = 1, 256, 2, 4, 8
    x = jax.random.normal(k[0], (B, S, H, Pd), jnp.float32)
    dt = jnp.full((B, S, H), 1.0, jnp.float32)       # cumsum reaches -256
    Bv = jax.random.normal(k[1], (B, S, N), jnp.float32)
    Cv = jax.random.normal(k[2], (B, S, N), jnp.float32)
    A = -jnp.ones((H,), jnp.float32)
    g = jax.grad(lambda x, dt: jnp.sum(
        ssd_chunked(x, dt, A, Bv, Cv, chunk=256)[0]), argnums=(0, 1))(x, dt)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in g)

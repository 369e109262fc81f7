"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(7)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 8, 2, 256, 64),     # GQA 4:1
    (1, 8, 1, 128, 128),    # MQA
    (1, 6, 6, 192, 32),     # non-pow2 heads/seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, KV, S, hd, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = flash_attention(q, k, v, interpret=True, block_q=64, block_k=64)
    ref = attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True,
                          block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ----------------------------------------------------------------------
# SSD scan
# ----------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (2, 128, 4, 16, 32, 32),
    (1, 256, 2, 64, 128, 64),
    (1, 64, 8, 32, 16, 64),   # single chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, S, H, P, N, Q, dtype):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 1).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bi = jax.random.normal(ks[3], (B, S, N), dtype)
    Ci = jax.random.normal(ks[4], (B, S, N), dtype)
    y, stt = ssd_scan(x, dt, A, Bi, Ci, chunk=Q, interpret=True)
    yr, str_ = ssd_ref(x, dt, A, Bi, Ci, Q)
    tol = 5e-4 if dtype == jnp.float32 else 1.5e-1  # bf16 inputs: long-chunk
    # decay chains accumulate rounding in both impls (f32 internals)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(stt, np.float32),
                               np.asarray(str_, np.float32), atol=tol, rtol=tol)


# ----------------------------------------------------------------------
# RG-LRU scan
# ----------------------------------------------------------------------

@pytest.mark.parametrize("B,S,W,bs,bw", [
    (2, 128, 64, 32, 32),
    (1, 256, 128, 64, 128),
    (3, 64, 32, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan(B, S, W, bs, bw, dtype):
    from repro.kernels.rg_lru.ops import rglru_scan
    from repro.kernels.rg_lru.ref import rglru_ref
    ks = jax.random.split(KEY, 2)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.98).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, W)) * 0.1).astype(dtype)
    h, hl = rglru_scan(a, b, block_s=bs, block_w=bw, interpret=True)
    hr, hlr = rglru_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(h, np.float32), np.asarray(hr),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(hl, np.float32), np.asarray(hlr),
                               atol=tol, rtol=tol)


# ----------------------------------------------------------------------
# blockwise quant
# ----------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(33, 77), (1024,), (5, 5, 5), (3000,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_kernel_matches_ref(shape, dtype):
    from repro.kernels.quant_blockwise.ops import dequantize, quantize
    x = jax.random.normal(KEY, shape, dtype)
    qk, sk = quantize(x, interpret=True)
    qr, sr = quantize(x)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    y = dequantize(qk, sk, shape, dtype, interpret=True)
    err = np.abs(np.asarray(y, np.float32) - np.asarray(x, np.float32))
    assert err.max() <= float(jnp.max(jnp.abs(x.astype(jnp.float32)))) / 127 + 1e-2


# ----------------------------------------------------------------------
# hash delta
# ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_hash_kernel_matches_ref(dtype):
    from repro.kernels.hash_delta.ops import tensor_digest
    if dtype == jnp.int32:
        x = jnp.arange(3000, dtype=dtype)
    else:
        x = jax.random.normal(KEY, (60, 50), dtype)
    hk = tensor_digest(x, interpret=True)
    hr = tensor_digest(x)
    assert int(hk) == int(hr)


def test_hash_sensitivity_and_order():
    from repro.kernels.hash_delta.ops import tensor_digest
    x = jax.random.normal(KEY, (128,), jnp.float32)
    h0 = int(tensor_digest(x))
    assert int(tensor_digest(x + 1e-3)) != h0
    perm = jnp.concatenate([x[1:], x[:1]])
    assert int(tensor_digest(perm)) != h0  # position-sensitive

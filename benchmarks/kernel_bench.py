"""Kernel micro-benches (XLA reference path on CPU; Pallas kernels are
TPU-targeted and validated in interpret mode by the tests — wall-clock numbers
here time the jnp oracle implementations the dry-run lowers)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

KEY = jax.random.PRNGKey(0)


def _time(fn, *args, iters=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    rows = []
    it = 1 if smoke else 5
    ks = jax.random.split(KEY, 5)

    from repro.kernels.flash_attention.ops import flash_attention
    B, H, S, hd = 1, 4, 512, 64
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32)
    us = _time(lambda *a: flash_attention(*a, impl="xla"), q, k, v, iters=it)
    flops = 4 * B * H * S * S * hd
    rows.append(("kernel/attention_ref_512", us, f"{flops/us/1e3:.1f} GFLOP/s"))

    from repro.kernels.ssd_scan.ops import ssd_scan
    x = jax.random.normal(ks[0], (1, 512, 8, 64), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 512, 8)))
    A = -jnp.exp(jax.random.normal(ks[2], (8,)) * 0.3)
    Bi = jax.random.normal(ks[3], (1, 512, 64), jnp.float32)
    Ci = jax.random.normal(ks[4], (1, 512, 64), jnp.float32)
    us = _time(lambda *a: ssd_scan(*a, chunk=128, impl="xla")[0], x, dt, A, Bi, Ci,
               iters=it)
    rows.append(("kernel/ssd_ref_512", us, "chunked SSD"))

    from repro.kernels.rg_lru.ops import rglru_scan
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 512, 256))) * 0.98
    b = jax.random.normal(ks[1], (2, 512, 256)) * 0.1
    us = _time(lambda *x: rglru_scan(*x, impl="xla")[0], a, b, iters=it)
    rows.append(("kernel/rglru_ref_512", us, "associative scan"))

    from repro.kernels.quant_blockwise.ops import quantize
    big = jax.random.normal(ks[0], (1024, 1024), jnp.float32)
    us = _time(lambda x: quantize(x)[0], big, iters=it)
    rows.append(("kernel/quant8_1M", us, f"{big.nbytes/us*1e6/1e9:.2f} GB/s"))

    from repro.kernels.hash_delta.ops import tensor_digest
    us = _time(lambda x: tensor_digest(x), big, iters=it)
    rows.append(("kernel/hash_1M", us, f"{big.nbytes/us*1e6/1e9:.2f} GB/s"))
    return rows


if __name__ == "__main__":
    for name, val, note in run():
        print(f"{name},{val:.1f},{note}")

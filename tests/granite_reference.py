"""Plain reference of the granite-4.0-h-small forward pass
(``granitemoehybrid``), for comparing the model library's logits.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
chunked scan.  Each layer is the published decoder layer,

    x = x + m * mixer(rmsnorm(x))
    x = x + m * (moe(rmsnorm(x)) + shared(rmsnorm(x)))

with ``m`` the residual multiplier and the mixer a Mamba-2 mixer or a GQA
attention layer with no positional encoding.  The Mamba-2 recurrence runs
token by token:

    h_t = exp(dt_t * A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

The router keeps each token's top-k logits of all its experts and takes
the softmax over those k; each expert's output is weighted by its gate
after its down projection.  The embedding is scaled by the embedding
multiplier, the scores by the attention multiplier, and the tied logits
are divided by the logits scaling.

``params`` is the model library's parameter tree (read by name, widened
to float32 one layer at a time, so that a full-width model fits) and
``cfg`` a mapping of its configuration's fields.  This module imports
nothing of the program.  Departures from the published description, each
shared with the program:

- RMSNorm scales by ``1 + w`` (weights stored as an offset from one).
- Expert share: a layer holds experts ``[first_expert, first_expert +
  num_experts)`` of the router's ``router_experts``; what experts held
  elsewhere would add is left out (expert parallelism adds it on other
  chips).
- Vocabulary slice: the embedding holds the rows of a slice of the
  vocabulary, and the logits are over that slice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512            # attention queries per block, so that scores fit


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _swiglu(x, w_gate, w_up, w_down):
    return (_silu(x @ w_gate) * (x @ w_up)) @ w_down


def mamba(p, x, c):
    """Mamba-2 mixer, x (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    din = c["ssm_expand"] * d
    P, N, W = c["ssm_headdim"], c["ssm_state"], c["conv_width"]
    H = din // P
    z = x @ p["w_z"]
    xbc = jnp.concatenate([x @ p["w_x"], x @ p["w_B"], x @ p["w_C"]], -1)
    # depthwise causal convolution over the last W inputs, bias, SiLU
    xp = jnp.concatenate([jnp.zeros((B, W - 1, xbc.shape[-1]), F32), xbc], 1)
    conv = sum(xp[:, k:k + S] * p["conv_w"][:, k] for k in range(W))
    xbc = _silu(conv + p["conv_b"])
    xs, Bm, Cm = xbc[..., :din], xbc[..., din:din + N], xbc[..., din + N:]
    dt = jax.nn.softplus(x @ p["w_dt"] + p["dt_bias"])           # (B,S,H)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(B, S, H, P)

    def step(h, inp):
        dt_t, x_t, B_t, C_t = inp
        h = (jnp.exp(dt_t * A)[:, :, None, None] * h
             + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, C_t)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (dt, xh, Bm, Cm))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32), seq)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xh
    y = _rmsnorm(y.reshape(B, S, din) * _silu(z), p["norm_w"], c["norm_eps"])
    return y @ p["w_out"]


def attention(p, x, c):
    """Causal GQA attention with no positional encoding, x (B, S, d)."""
    B, S, d = x.shape
    H, KV = c["num_heads"], c["num_kv_heads"]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), H // KV, 2)
    v = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), H // KV, 2)
    scale = c["attention_multiplier"] or q.shape[-1] ** -0.5
    out = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        s = jnp.einsum("bqhk,bshk->bhqs", qb, k) * scale
        causal = (q0 + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(S)
        s = jnp.where(causal, s, -jnp.inf)
        out.append(jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v))
    return jnp.einsum("bshk,hkd->bsd", jnp.concatenate(out, 1), p["wo"])


def moe(p, x, c):
    """Routed experts held here plus the shared expert, x (B, S, d)."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    top, idx = jax.lax.top_k(xt @ p["router"], c["experts_per_tok"])
    gate = jax.nn.softmax(top, -1)
    out = _swiglu(xt, p["shared"]["w_gate"], p["shared"]["w_up"],
                  p["shared"]["w_down"])
    for j in range(c["num_experts"]):
        e = c.get("first_expert", 0) + j
        g = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
        y = _swiglu(xt, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
        out = out + g[:, None] * y
    return out.reshape(B, S, d)


def layer(kind, p, x, c):
    eps, m = c["norm_eps"], c["residual_multiplier"]
    if kind == "ssm":
        x = x + m * mamba(p["mixer"], _rmsnorm(x, p["ln"], eps), c)
    else:
        x = x + m * attention(p["attn"], _rmsnorm(x, p["ln1"], eps), c)
    return x + m * moe(p["ffn"], _rmsnorm(x, p["ln2"], eps), c)


def layers(params, c):
    """(kind, parameters) of every layer in depth order, parameters as
    stored (the group axis taken off)."""
    pat = list(c["block_pattern"])
    groups = c["num_layers"] // len(pat)
    dec = params["decoder"]
    for g in range(groups):
        for i, kind in enumerate(pat):
            yield kind, jax.tree_util.tree_map(
                lambda a: a[g], dec["groups"][f"b{i}_{kind}"])
    tail = [pat[i % len(pat)] for i in range(groups * len(pat),
                                             c["num_layers"])]
    for i, kind in enumerate(tail):
        yield kind, dec["tail"][f"t{i}_{kind}"]


def forward(params, tokens, cfg, last: int | None = None, weights=None):
    """Float32 logits (B, S, V) of ``tokens`` (B, S); only the ``last``
    positions when given.  ``weights``, a dtype, rounds every parameter
    through it before it is widened (a control: the reference computed
    from weights in a precision below the stated one)."""
    c = dict(cfg)

    def widen(t):
        def one(a):
            if weights is not None and jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(weights)
            return a.astype(F32)
        return jax.tree_util.tree_map(one, t)

    with jax.default_matmul_precision("highest"):
        step = {kind: jax.jit(lambda p, x, kind=kind: layer(
            kind, widen(p), x, c)) for kind in set(c["block_pattern"])}
        table = widen(params["embed"])
        x = jnp.take(table, tokens, axis=0) * c["embedding_multiplier"]
        for kind, p in layers(params, c):
            x = step[kind](p, x)
        if last is not None:
            x = x[:, -last:]
        x = _rmsnorm(x, widen(params["ln_f"]), c["norm_eps"])
        return (x @ table.T) / c["logits_scaling"]


def rel_err(a, b):
    """Relative L2 error of logits ``a`` against ``b`` along the
    vocabulary, per position: |a - b| / |b|."""
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return jnp.linalg.norm(a - b, axis=-1) / jnp.linalg.norm(b, axis=-1)

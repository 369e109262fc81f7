"""Parameter-spec system and shared layer primitives.

A model is described once as a tree of :class:`P` leaves (shape + logical
axes + init rule).  From that single source of truth we derive:

- real parameters (``init_params``) for smoke tests / examples,
- abstract ``ShapeDtypeStruct`` trees (``abstract_params``) for the dry-run
  (never allocates),
- logical-axis trees (``axes_tree``) feeding the sharding rules.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class P:
    """Declarative parameter leaf."""
    shape: tuple
    axes: tuple                 # logical axis names, len == len(shape)
    init: str = "normal"        # normal | zeros | ones | lecun | dt_bias | a_log | lambda
    scale: float | None = None  # stddev override for normal init

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_p(x) -> bool:
    return isinstance(x, P)


def _leaves(spec) -> list[tuple[str, P]]:
    flat = jax.tree_util.tree_flatten_with_path(spec, is_leaf=is_p)[0]
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _materialize(p: P, key, dtype) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "dt_bias":  # mamba2 dt bias: log-uniform dt in [1e-3, 1e-1]
        u = jax.random.uniform(key, p.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(dtype)  # inverse softplus
    if p.init == "a_log":    # mamba2 A in [1, 16]
        return jnp.log(jax.random.uniform(key, p.shape, jnp.float32, 1.0, 16.0)).astype(dtype)
    if p.init == "lambda":   # RG-LRU Lambda parameter: a in [0.9, 0.999]
        a = jax.random.uniform(key, p.shape, jnp.float32, 0.9, 0.999)
        # a = sigmoid(L)^c with c=8 -> L = logit(a**(1/8))
        r = a ** (1.0 / 8.0)
        return jnp.log(r / (1 - r)).astype(dtype)
    fan_in = p.shape[0] if len(p.shape) == 1 else int(np.prod(p.shape[:-1]))
    if len(p.shape) >= 3 and p.axes[0] in ("layers", "groups", "experts"):
        fan_in = int(np.prod(p.shape[1:-1])) or 1
    std = p.scale if p.scale is not None else 1.0 / max(np.sqrt(fan_in), 1.0)
    return (jax.random.truncated_normal(key, -2.0, 2.0, p.shape, jnp.float32) * std).astype(dtype)


def init_params(spec, key, dtype=jnp.bfloat16):
    """Materialize a spec tree into real arrays (deterministic per path)."""
    named = _leaves(spec)
    keys = jax.random.split(key, max(len(named), 1))
    table = {name: _materialize(p, k, dtype) for (name, p), k in zip(named, keys)}
    it = iter(range(len(named)))
    return jax.tree_util.tree_map(
        lambda p: table[named[next(it)][0]], spec, is_leaf=is_p)


def abstract_params(spec, dtype=jnp.bfloat16):
    return jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), spec, is_leaf=is_p)


def axes_tree(spec):
    return jax.tree_util.tree_map(lambda p: p.axes, spec, is_leaf=is_p)


def param_count(spec) -> int:
    return sum(int(np.prod(p.shape)) for _, p in _leaves(spec))


def stack_spec(spec, n: int, axis_name: str = "layers"):
    """Prepend a stacking dim (for scan-over-layers parameter stacks)."""
    return jax.tree_util.tree_map(
        lambda p: P((n,) + p.shape, (axis_name,) + p.axes, p.init, p.scale),
        spec, is_leaf=is_p)


# ======================================================================
# Numerics primitives
# ======================================================================

def rms_norm(x, weight, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))
    return out.astype(x.dtype)


def head_rms_norm(x, weight, eps: float):
    """Per-head q/k norm (qwen3): x (..., hd), weight (hd,)."""
    return rms_norm(x, weight, eps)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", silu(g) * u, w_down)


# ----------------------------------------------------------------------
# RoPE (supports partial rotary: stablelm rope_pct=0.25)
# ----------------------------------------------------------------------

def rope_freqs(head_dim: int, rope_pct: float, theta: float):
    rot = int(head_dim * rope_pct)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, *, rope_pct: float = 1.0, theta: float = 10_000.0):
    """x: (..., S, H, hd) or (..., S, hd); positions: (..., S)."""
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, rope_pct, theta)
    if rot == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == positions.ndim + 2:  # head axis present
        cos, sin = cos[..., None, :], sin[..., None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------
# Depthwise causal conv1d (mamba2 / RG-LRU frontends)
# ----------------------------------------------------------------------

def causal_conv1d(x, w, state=None, activation: bool = True, bias=None):
    """x: (B, S, C); w: (C, W) depthwise causal filter; bias (C,) or None.

    Returns (y, new_state) where state (B, W-1, C) carries the last W-1 inputs
    (used for decode).  Training path pads with zeros (state None).
    ``activation=True`` applies SiLU (mamba2 convention); RG-LRU convs are
    linear (``activation=False``).
    """
    B, S, C = x.shape
    W = w.shape[-1]
    if state is None:
        pad = jnp.zeros((B, W - 1, C), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S+W-1, C)
    # stack W shifted views: y_t = sum_k w[:, k] * x_{t-W+1+k}
    y = jnp.zeros_like(x)
    for k in range(W):
        y = y + xp[:, k:k + S, :] * w[:, k].astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    new_state = xp[:, S:, :] if W > 1 else jnp.zeros((B, 0, C), x.dtype)
    return (silu(y) if activation else y), new_state


# ----------------------------------------------------------------------
# Embedding / logits / loss (with vocab padding + optional vocab-parallel)
# ----------------------------------------------------------------------

def embed_spec(cfg):
    return P((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), scale=0.02)


def embed_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


def logits_from_embed(x, table):
    return jnp.einsum("...d,vd->...v", x, table)


def softmax_xent(logits, labels, vocab_size: int):
    """Mean CE over tokens; padded-vocab columns masked out. fp32 internally."""
    lf = logits.astype(jnp.float32)
    V = lf.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    lf = jnp.where(col < vocab_size, lf, -1e30)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(lse - gold)

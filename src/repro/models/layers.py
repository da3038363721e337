"""Common layers: norms, RoPE, MLPs, embeddings (pure JAX, P-leaf params)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import P, shard


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, axes, in_axis=0, dtype=jnp.bfloat16) -> P:
    fan_in = shape[in_axis]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    w = jax.random.normal(key, shape, dtype=jnp.float32) * scale
    return P(w.astype(dtype), axes)


def zeros_init(shape, axes, dtype=jnp.bfloat16) -> P:
    return P(jnp.zeros(shape, dtype=dtype), axes)


def ones_init(shape, axes, dtype=jnp.float32) -> P:
    return P(jnp.ones(shape, dtype=dtype), axes)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, with_bias: Optional[bool] = None):
    bias = cfg.norm == "layernorm" if with_bias is None else with_bias
    p = {"scale": ones_init((cfg.d_model,), ("embed_act",))}
    if bias:
        p["bias"] = P(jnp.zeros((cfg.d_model,), jnp.float32), ("embed_act",))
    return p


def apply_norm(params, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
    y = y * params["scale"]
    if "bias" in params:
        y = y + params["bias"]
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                      # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..,S,half)
    cos = jnp.cos(angles)[..., :, None, :]                   # (..,S,1,half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# MLP (gated and plain)
# --------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    gated = cfg.act in ("silu", "geglu")
    p = {
        "wi": dense_init(ks[0], (cfg.d_model, d_ff), ("embed", "mlp"), dtype=dt),
        "wo": dense_init(ks[1], (d_ff, cfg.d_model), ("mlp", "embed"), dtype=dt),
    }
    if gated:
        p["wg"] = dense_init(ks[2], (cfg.d_model, d_ff), ("embed", "mlp"),
                             dtype=dt)
    return p


def apply_mlp(params, x, cfg: ModelConfig):
    h = x @ params["wi"]
    if cfg.act == "silu":
        h = jax.nn.silu(x @ params["wg"]) * h
    elif cfg.act == "geglu":
        h = jax.nn.gelu(x @ params["wg"]) * h
    else:
        h = jax.nn.gelu(h)
    h = shard(h, "batch", "seq", "mlp")
    return h @ params["wo"]


# --------------------------------------------------------------------------
# Embeddings
# --------------------------------------------------------------------------

def init_embedding(cfg: ModelConfig, key):
    dt = _dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"tok": dense_init(k1, (cfg.padded_vocab, cfg.d_model),
                           ("vocab", "embed"), in_axis=1, dtype=dt)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k2, (cfg.d_model, cfg.padded_vocab),
                               ("embed", "vocab"), dtype=dt)
    if cfg.pos_emb == "learned":
        max_pos = max(cfg.encoder_seq, 32_768) if cfg.is_encoder_decoder else 32_768
        p["pos"] = dense_init(k3, (max_pos, cfg.d_model), (None, "embed"),
                              in_axis=1, dtype=dt)
    return p


def embed_tokens(params, tokens, cfg: ModelConfig,
                 positions: Optional[jnp.ndarray] = None):
    x = jnp.take(params["tok"], tokens, axis=0)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.pos_emb == "learned" and positions is not None:
        npos = params["pos"].shape[0]
        x = x + jnp.take(params["pos"], jnp.clip(positions, 0, npos - 1),
                         axis=0)
    return shard(x, "batch", "seq", "embed_act")


def lm_head(params, x, cfg: ModelConfig):
    """Logits in float32 (f32 accumulation of the bf16 matmul): rounded
    to bf16, logits of a 50k vocab tie exactly often enough that greedy
    argmax depends on which path computed them."""
    w = params["tok"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return shard(logits, "batch", "seq", "vocab")

"""Model façade: init / forward / prefill / decode for every arch family.

``batch`` dicts:
  dense|moe|ssm|hybrid: {"tokens": (B, S) int32}
  audio (whisper):      {"frames": (B, encoder_seq, D), "tokens": (B, S)}
  vlm (pixtral):        {"patches": (B, num_patches, D), "tokens": (B, S-P)}

Decode caches are family-specific pytrees created by ``init_decode_cache``
(zeros; pos slots -1) so `jax.eval_shape` can derive dry-run specs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import P, shard
from repro.models import attention as attn
from repro.models import encdec as encdec_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import embed_tokens, lm_head


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def init(cfg: ModelConfig, key) -> Dict:
    if cfg.family in ("ssm", "hybrid"):
        return tfm.init_ssm_lm(cfg, key)
    if cfg.family == "audio":
        return encdec_mod.init_encdec(cfg, key)
    return tfm.init_lm(cfg, key)


# --------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# --------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, batch: Dict, *,
            return_cache: bool = False, remat: bool = False,
            window: Optional[int] = None):
    """Returns (logits, cache, aux_loss)."""
    if cfg.family == "audio":
        memory = encdec_mod.encode(params, batch["frames"], cfg)
        x, cache = encdec_mod.decoder_forward(params, batch["tokens"], memory,
                                              cfg, return_cache=return_cache,
                                              remat=remat)
        logits = lm_head(params["embed"], x, cfg)
        if return_cache:
            cache = {"self": cache,
                     "cross": encdec_mod.build_cross_cache(params, memory, cfg)}
        return logits, cache, 0.0

    tokens = batch["tokens"]
    B, S_tok = tokens.shape
    pos_tok = jnp.broadcast_to(jnp.arange(S_tok, dtype=jnp.int32), (B, S_tok))

    if cfg.family == "vlm":
        patches = batch["patches"].astype(jnp.dtype(cfg.dtype))
        Pn = patches.shape[1]
        x_tok = embed_tokens(params["embed"], tokens, cfg)
        x = jnp.concatenate([patches, x_tok], axis=1)
        S = Pn + S_tok
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    else:
        x = embed_tokens(params["embed"], tokens, cfg, positions=pos_tok)
        positions = pos_tok

    if cfg.family in ("ssm", "hybrid"):
        h, cache, aux = tfm.ssm_backbone_forward(
            params, x, cfg, positions, return_cache=return_cache,
            remat=remat, window=window)
    else:
        h, cache, aux = tfm.backbone_forward(
            params, x, cfg, positions, window=window,
            return_cache=return_cache, remat=remat)
    logits = lm_head(params["embed"], h, cfg)
    return logits, cache, aux


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------

def decode_step(cfg: ModelConfig, params, tokens, cache, cur_pos, *,
                window: Optional[int] = None):
    """tokens: (B, 1); cur_pos: (B,).  Returns (logits, new_cache)."""
    if cfg.family == "audio":
        x, new_self = encdec_mod.decoder_decode(
            params, tokens, cfg, cache["self"], cache["cross"], cur_pos)
        logits = lm_head(params["embed"], x, cfg)
        return logits, {"self": new_self, "cross": cache["cross"]}

    x = embed_tokens(params["embed"], tokens, cfg,
                     positions=cur_pos[:, None])
    if cfg.family in ("ssm", "hybrid"):
        h, new_cache = tfm.ssm_backbone_decode(params, x, cfg, cache,
                                               cur_pos, window=window)
    else:
        h, new_cache = tfm.backbone_decode(params, x, cfg, cache, cur_pos,
                                           window=window)
    logits = lm_head(params["embed"], h, cfg)
    return logits, new_cache


# --------------------------------------------------------------------------
# Decode-cache construction
# --------------------------------------------------------------------------

def _tile(tree, n):
    return jax.tree.map(lambda a: jnp.tile(a, (n,) + (1,) * a.ndim), tree)


def init_decode_cache(cfg: ModelConfig, batch: int, max_seq: int,
                      window: Optional[int] = None) -> Any:
    if cfg.family == "audio":
        one = attn.init_cache(cfg, batch, max_seq, window)
        cross = {
            "k": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                            cfg.num_kv_heads, cfg.head_dim),
                           jnp.dtype(cfg.dtype)),
            "v": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                            cfg.num_kv_heads, cfg.head_dim),
                           jnp.dtype(cfg.dtype)),
        }
        return {"self": _tile(one, cfg.num_layers), "cross": cross}
    if cfg.family == "ssm":
        return {"ssm": _tile(ssm_mod.init_ssm_cache(cfg, batch),
                             cfg.num_layers)}
    if cfg.family == "hybrid":
        # a stacked state per group of Mamba2 layers, and one KV cache per
        # shared-block use
        one = ssm_mod.init_ssm_cache(cfg, batch)
        return {
            "ssm": [_tile(one, hi - lo)
                    for lo, hi, _ in tfm.hybrid_groups(cfg)],
            "attn": _tile(attn.init_cache(cfg, batch, max_seq, window),
                          len(cfg.hybrid_layer_ids)),
        }
    one = attn.init_cache(cfg, batch, max_seq, window)
    out = {}
    n_dense = cfg.num_dense_layers if cfg.num_experts else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.num_experts else 0
    if n_dense:
        out["dense"] = _tile(one, n_dense)
    if n_moe:
        out["moe"] = _tile(one, n_moe)
    return out


def merge_prefill_cache(decode_cache, prefill_cache):
    """Write a prefill-produced cache into (larger) decode-cache slots.

    Leaves with identical shapes are replaced; leaves differing along one
    axis (the sequence axis) are written at offset 0 of that axis.
    """
    def merge(dst, src):
        src = src.astype(dst.dtype)
        if dst.shape == src.shape:
            return src
        diff = [i for i, (a, b) in enumerate(zip(dst.shape, src.shape))
                if a != b]
        assert len(diff) == 1, (dst.shape, src.shape)
        idx = tuple(0 for _ in dst.shape)
        return jax.lax.dynamic_update_slice(dst, src, idx)

    return jax.tree.map(merge, decode_cache, prefill_cache)


def cache_logical_axes(cache) -> Any:
    """Map a decode-cache pytree to logical axis tuples (by leaf name/rank)."""
    def walk(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1] if names else ""
        extra = ("layer",)  # leading stacked-layer axis
        if name in ("k", "v"):
            if "cross" in names:
                # encoder cross-KV: fixed encoder_seq (e.g. 1500) — not
                # shardable over the data axes; replicate the seq dim
                return extra + ("batch", None, "kv_heads", "head_dim")
            return extra + ("batch", "kv_seq", "kv_heads", "head_dim")
        if name == "ckv":
            return extra + ("batch", "kv_seq", "lora")
        if name == "krope":
            return extra + ("batch", "kv_seq", None)
        if name == "pos":
            return extra + ("batch", "kv_seq")
        if name == "conv":
            return extra + ("batch", None, "ssm_inner")
        if name == "ssm":
            return extra + ("batch", "ssm_heads", None, "state")
        return tuple([None] * leaf.ndim)

    return jax.tree_util.tree_map_with_path(walk, cache)


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------

def lm_loss(cfg: ModelConfig, logits, batch) -> jnp.ndarray:
    """Next-token cross-entropy (fp32, stable); VLM: text positions only."""
    tokens = batch["tokens"]
    if cfg.family == "vlm":
        logits = logits[:, batch["patches"].shape[1]:, :]
    lg = logits[:, :-1, :].astype(jnp.float32)
    tg = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(cfg: ModelConfig, params, batch, *, remat: bool = False):
    logits, _, aux = forward(cfg, params, batch, remat=remat)
    return lm_loss(cfg, logits, batch) + aux


# --------------------------------------------------------------------------
# Input construction (shared by tests / launch / engine)
# --------------------------------------------------------------------------

def make_inputs(cfg: ModelConfig, batch: int, seq_len: int, *,
                abstract: bool = False, key=None) -> Dict:
    """Concrete (random) or abstract (ShapeDtypeStruct) model inputs."""
    dt = jnp.dtype(cfg.dtype)

    def tok(shape):
        if abstract:
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        k = key if key is not None else jax.random.PRNGKey(0)
        return jax.random.randint(k, shape, 0, cfg.vocab_size, jnp.int32)

    def emb(shape):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        k = key if key is not None else jax.random.PRNGKey(1)
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    if cfg.family == "audio":
        return {"frames": emb((batch, cfg.encoder_seq, cfg.d_model)),
                "tokens": tok((batch, seq_len))}
    if cfg.family == "vlm":
        Pn = min(cfg.num_patches, max(1, seq_len // 4))
        return {"patches": emb((batch, Pn, cfg.d_model)),
                "tokens": tok((batch, seq_len - Pn))}
    return {"tokens": tok((batch, seq_len))}

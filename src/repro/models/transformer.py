"""Decoder-only LM backbones: dense / MoE / SSM / hybrid.

Homogeneous layer stacks are initialized one layer at a time with
``jax.lax.map`` (stacked leaves, leading "layer" axis) and executed with
``jax.lax.scan`` so HLO size is depth-independent.  ``remat`` wraps the scanned block when requested
(activation-checkpoint policy is a hillclimb knob).

``init_*`` functions return P-leaf trees (value + logical axes); ``apply``
functions take plain array trees (see ``repro.dist.sharding.unbox``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import P, shard
from repro.models import attention as attn
from repro.models import flags
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, embed_tokens,
                                 init_embedding, init_mlp, init_norm, lm_head)


def stack_init(init_fn, key, n: int, axis_name: Optional[str] = None):
    """Map an init over n keys; prepend a layer axis to every P leaf.

    ``lax.map`` draws one layer per step, so only that layer's float32
    draws are live next to the stacked (bf16) result.  A ``vmap`` would
    draw the whole stack in float32 first: 10.9 GB for one StarCoder2-7B
    FFN weight, more than a 16 GB chip has left beside the weights."""
    keys = jax.random.split(key, n)
    stacked = jax.lax.map(init_fn, keys)
    return jax.tree.map(
        lambda p: P(p.value, (axis_name,) + p.axes),
        stacked, is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# Attention/FFN block (dense + MoE)
# --------------------------------------------------------------------------

def init_block(cfg: ModelConfig, key, moe_layer: bool) -> Dict:
    k1, k2 = jax.random.split(key)
    p = {
        "norm1": init_norm(cfg),
        "attn": attn.init_attention(cfg, k1),
        "norm2": init_norm(cfg),
    }
    if moe_layer:
        p["moe"] = moe_mod.init_moe(cfg, k2)
    else:
        p["mlp"] = init_mlp(cfg, k2)
    return p


def apply_block(params, x, cfg: ModelConfig, positions, *,
                window: Optional[int] = None, return_cache: bool = False):
    """Full-sequence block.  Returns (x, cache, aux)."""
    h = apply_norm(params["norm1"], x, cfg)
    a, cache = attn.attention_forward(params["attn"], h, cfg, positions,
                                      return_cache=return_cache,
                                      window=window)
    x = x + a
    h = apply_norm(params["norm2"], x, cfg)
    if "moe" in params:
        f, aux = moe_mod.apply_moe(params["moe"], h, cfg)
    else:
        f, aux = apply_mlp(params["mlp"], h, cfg), 0.0
    x = x + f
    return shard(x, "batch", "seq", "embed_act"), cache, aux


def apply_block_decode(params, x, cfg: ModelConfig, cache, cur_pos, *,
                       window: Optional[int] = None):
    """One block of a decode step.  Returns (x, rows): the new token's
    cache rows (``attn.attention_decode``); ``cache`` is only read."""
    h = apply_norm(params["norm1"], x, cfg)
    a, rows = attn.attention_decode(params["attn"], h, cfg, cache,
                                    cur_pos, window=window)
    x = x + a
    h = apply_norm(params["norm2"], x, cfg)
    if "moe" in params:
        f, _ = moe_mod.apply_moe(params["moe"], h, cfg, decode=True)
    else:
        f = apply_mlp(params["mlp"], h, cfg)
    return x + f, rows


# --------------------------------------------------------------------------
# SSM block
# --------------------------------------------------------------------------

def init_ssm_block(cfg: ModelConfig, key) -> Dict:
    return {"norm": init_norm(cfg), "mixer": ssm_mod.init_ssm(cfg, key)}


def apply_ssm_block(params, x, cfg, *, return_cache=False, cache=None):
    h = apply_norm(params["norm"], x, cfg)
    if cache is None:
        y, new_cache = ssm_mod.ssm_forward(params["mixer"], h, cfg,
                                           return_cache=return_cache)
    else:
        y, new_cache = ssm_mod.ssm_decode(params["mixer"], h, cfg, cache)
    return x + y, new_cache


# --------------------------------------------------------------------------
# Dense / MoE decoder-only LM
# --------------------------------------------------------------------------

def init_lm(cfg: ModelConfig, key) -> Dict:
    ke, kd, km = jax.random.split(key, 3)
    p: Dict[str, Any] = {"embed": init_embedding(cfg, ke),
                         "final_norm": init_norm(cfg)}
    n_dense = cfg.num_dense_layers if cfg.num_experts else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.num_experts else 0
    if n_dense:
        p["dense_layers"] = stack_init(
            lambda k: init_block(cfg, k, moe_layer=False), kd, n_dense)
    if n_moe:
        p["moe_layers"] = stack_init(
            lambda k: init_block(cfg, k, moe_layer=True), km, n_moe)
    return p


def _scan_stack(layer_params, x, fn, caches=None, remat: bool = False):
    """Scan fn over a stacked layer tree; ``caches`` (stacked like the
    layers) are handed to fn layer by layer.  Returns (x, what fn emitted
    per layer, stacked; aux)."""
    if remat:
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)

    if caches is None:
        def step(carry, lp):
            y, cache, aux = fn(lp, carry)
            return y, (cache, aux)
        x, (cache_stack, aux) = jax.lax.scan(step, x, layer_params,
                                             unroll=flags.scan_unroll())
    else:
        def step(carry, inp):
            lp, c = inp
            y, cache, aux = fn(lp, carry, c)
            return y, (cache, aux)
        x, (cache_stack, aux) = jax.lax.scan(step, x, (layer_params, caches),
                                             unroll=flags.scan_unroll())
    return x, cache_stack, aux


def backbone_forward(params, x, cfg: ModelConfig, positions, *,
                     window: Optional[int] = None, return_cache: bool = False,
                     remat: bool = False):
    """x: (B, S, D) embeddings -> (hidden, cache_dict, aux_loss)."""
    caches = {}
    aux_total = 0.0

    def blk(lp, h):
        y, c, aux = apply_block(lp, h, cfg, positions, window=window,
                                return_cache=return_cache)
        return y, (c if return_cache else 0), aux

    if "dense_layers" in params:
        x, c, aux = _scan_stack(params["dense_layers"], x, blk, remat=remat)
        caches["dense"] = c
        aux_total += jnp.sum(aux) if cfg.num_experts else 0.0
    if "moe_layers" in params:
        x, c, aux = _scan_stack(params["moe_layers"], x, blk, remat=remat)
        caches["moe"] = c
        aux_total = aux_total + jnp.sum(aux)
    x = apply_norm(params["final_norm"], x, cfg)
    return x, (caches if return_cache else None), aux_total


def backbone_decode(params, x, cfg: ModelConfig, cache, cur_pos, *,
                    window: Optional[int] = None):
    """The layer scan reads the stacked cache and emits only each layer's
    new rows; one write after it puts them into the cache."""
    def blk(lp, h, c):
        y, rows = apply_block_decode(lp, h, cfg, c, cur_pos, window=window)
        return y, rows, 0.0

    new_cache = {}
    for name, stack in (("dense", "dense_layers"), ("moe", "moe_layers")):
        if stack in params:
            x, rows, _ = _scan_stack(params[stack], x, blk,
                                     caches=cache[name])
            new_cache[name] = attn.write_decode_rows(cache[name], rows,
                                                     cur_pos)
    x = apply_norm(params["final_norm"], x, cfg)
    return x, new_cache


# --------------------------------------------------------------------------
# SSM / hybrid LM
# --------------------------------------------------------------------------

def init_ssm_lm(cfg: ModelConfig, key) -> Dict:
    ke, kl, ka = jax.random.split(key, 3)
    p = {"embed": init_embedding(cfg, ke),
         "final_norm": init_norm(cfg),
         "layers": stack_init(lambda k: init_ssm_block(cfg, k), kl,
                              cfg.num_layers)}
    if cfg.attn_every:  # hybrid: one weight-shared attention block
        p["shared_attn"] = init_block(cfg, ka, moe_layer=False)
    return p


def _hybrid_groups(cfg: ModelConfig):
    n, k = cfg.num_layers, cfg.attn_every
    bounds = []
    i = 0
    while i < n:
        bounds.append((i, min(i + k, n)))
        i += k
    return bounds


def ssm_backbone_forward(params, x, cfg: ModelConfig, positions, *,
                         return_cache: bool = False, remat: bool = False,
                         window: Optional[int] = None):
    def blk(lp, h):
        y, c = apply_ssm_block(lp, h, cfg, return_cache=return_cache)
        return y, (c if return_cache else 0), 0.0

    caches: Dict[str, Any] = {}
    if not cfg.attn_every:
        x, c, _ = _scan_stack(params["layers"], x, blk, remat=remat)
        caches["ssm"] = c
    else:
        ssm_caches, attn_caches = [], []
        for (lo, hi) in _hybrid_groups(cfg):
            seg = jax.tree.map(lambda a: a[lo:hi], params["layers"])
            x, c, _ = _scan_stack(seg, x, blk, remat=remat)
            ssm_caches.append(c)
            x, ac, _ = apply_block(params["shared_attn"], x, cfg, positions,
                                   window=window, return_cache=return_cache)
            attn_caches.append(ac)
        if return_cache:
            caches["ssm"] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *ssm_caches)
            caches["attn"] = jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=0), *attn_caches)
    x = apply_norm(params["final_norm"], x, cfg)
    return x, (caches if return_cache else None), 0.0


def ssm_backbone_decode(params, x, cfg: ModelConfig, cache, cur_pos, *,
                        window: Optional[int] = None):
    def blk(lp, h, c):
        y, nc = apply_ssm_block(lp, h, cfg, cache=c)
        return y, nc, 0.0

    new_cache: Dict[str, Any] = {}
    if not cfg.attn_every:
        x, c, _ = _scan_stack(params["layers"], x, blk, caches=cache["ssm"])
        new_cache["ssm"] = c
    else:
        ssm_caches, attn_rows = [], []
        for gi, (lo, hi) in enumerate(_hybrid_groups(cfg)):
            seg = jax.tree.map(lambda a: a[lo:hi], params["layers"])
            cseg = jax.tree.map(lambda a: a[lo:hi], cache["ssm"])
            x, c, _ = _scan_stack(seg, x, blk, caches=cseg)
            ssm_caches.append(c)
            ac = jax.tree.map(lambda a: a[gi], cache["attn"])
            x, rows = apply_block_decode(params["shared_attn"], x, cfg, ac,
                                         cur_pos, window=window)
            attn_rows.append(rows)
        new_cache["ssm"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *ssm_caches)
        new_cache["attn"] = attn.write_decode_rows(
            cache["attn"],
            jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *attn_rows),
            cur_pos)
    x = apply_norm(params["final_norm"], x, cfg)
    return x, new_cache

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
from repro.models.layers import (apply_mlp, apply_norm, dense_init,
                                 embed_tokens, init_embedding, init_mlp,
                                 init_norm, lm_head)


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


def apply_ssm_block(params, x, cfg, *, t=None, return_cache=False,
                    cache=None):
    """x + Mamba2(norm(x)), or x + Mamba2(norm(x + t)) where a Zamba2
    shared block's output t enters the layer (not the residual)."""
    h = apply_norm(params["norm"], x if t is None else x + t, cfg)
    if cache is None:
        y, new_cache = ssm_mod.ssm_forward(params["mixer"], h, cfg,
                                           return_cache=return_cache)
    else:
        y, new_cache = ssm_mod.ssm_decode(params["mixer"], h, cfg, cache)
    return x + y, new_cache


def _ssm_scan(layers, x, cfg, *, t=None, return_cache=False,
              remat=False):
    """Full-sequence scan over a stack of Mamba2 layers; ``t`` enters the
    first layer only.  Returns (x, per-layer caches stacked | None)."""
    def step(carry, lp):
        h, tc = carry
        y, c = apply_ssm_block(lp, h, cfg, t=tc, return_cache=return_cache)
        return (y, None if tc is None else jnp.zeros_like(tc)), \
            (c if return_cache else 0)

    if remat:
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies.nothing_saveable)
    (x, _), caches = jax.lax.scan(step, (x, t), layers,
                                  unroll=flags.scan_unroll())
    return x, (caches if return_cache else None)


def _ssm_scan_decode(layers, x, cfg, state, *, t=None):
    """One decode step over a stack of Mamba2 layers.  The stacked state
    (conv and SSM, leading layer axis) rides in the scan's carry: each
    layer reads its slice and writes its new one in place, so a donated
    state is neither copied nor emitted a second time."""
    def step(carry, inp):
        h, tc, st = carry
        lp, i = inp
        mine = jax.tree.map(lambda a: a[i], st)
        y, new = apply_ssm_block(lp, h, cfg, t=tc, cache=mine)
        st = jax.tree.map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(
                a, b.astype(a.dtype), i, 0), st, new)
        return (y, None if tc is None else jnp.zeros_like(tc), st), None

    n = jax.tree.leaves(layers)[0].shape[0]
    (x, _, state), _ = jax.lax.scan(
        step, (x, t, state), (layers, jnp.arange(n)),
        unroll=flags.scan_unroll())
    return x, state


# --------------------------------------------------------------------------
# Zamba2 shared block
# --------------------------------------------------------------------------

def init_shared_block(cfg: ModelConfig, key) -> Dict:
    """One of Zamba2's ``num_mem_blocks`` shared attention+MLP blocks."""
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm_in": {"scale": P(jnp.ones((2 * d,), jnp.float32),
                               ("embed_act",))},
        "attn": attn.init_attention(cfg, k1, d_in=2 * d),
        "norm_ff": init_norm(cfg),
        "mlp": {"w_gate_up": dense_init(k2, (d, 2 * cfg.d_ff),
                                        ("embed", "mlp"), dtype=dt),
                "w_down": dense_init(k3, (cfg.d_ff, d), ("mlp", "embed"),
                                     dtype=dt)},
    }


def init_hybrid_use(cfg: ModelConfig, key) -> Dict:
    """What each use of a shared block owns: the LoRA on its MLP's
    gate/up projection and the linear after the block."""
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {"lora_a": dense_init(k1, (d, cfg.adapter_rank),
                                 ("embed", None), dtype=dt),
            "lora_b": dense_init(k2, (cfg.adapter_rank, 2 * cfg.d_ff),
                                 (None, "mlp"), dtype=dt),
            "linear": dense_init(k3, (d, d), ("embed", None), dtype=dt)}


def apply_shared_block(block, use, x, x0, cfg: ModelConfig, positions, *,
                       return_cache: bool = False, cache=None):
    """Zamba2's shared block, with no residual: [h, x0] through the norm,
    the attention, the norm and the MLP (gelu(g) * up, from [g, up] =
    m W_gu + (m A_use) B_use), then W_down and the use's linear.  Returns
    (t, KV) for the next Mamba2 layer: the block's attention cache over
    the sequence, or with ``cache`` (this use's, only read; ``positions``
    then cur_pos) one decode step's new KV rows."""
    with jax.named_scope("zamba2.shared"):
        u = apply_norm(block["norm_in"], jnp.concatenate([x, x0], -1), cfg)
        if cache is None:
            o, kv = attn.attention_forward(block["attn"], u, cfg, positions,
                                           return_cache=return_cache)
        else:
            o, kv = attn.attention_decode(block["attn"], u, cfg, cache,
                                          positions)
        m = apply_norm(block["norm_ff"], o, cfg)
        mp = block["mlp"]
        gu = m @ mp["w_gate_up"] + (m @ use["lora_a"]) @ use["lora_b"]
        g, up = jnp.split(gu, 2, axis=-1)
        h = jax.nn.gelu(g, approximate=False) * up
        return (h @ mp["w_down"]) @ use["linear"], kv


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

def hybrid_groups(cfg: ModelConfig):
    """The hybrid's Mamba2 layers as scanned groups: (lo, hi, use) for
    layers lo..hi-1, where ``use`` is the index of the shared-block use
    whose output enters layer lo (None for leading plain layers)."""
    ids = cfg.hybrid_layer_ids
    bounds = sorted({0, *ids}) + [cfg.num_layers]
    return [(lo, hi, ids.index(lo) if lo in ids else None)
            for lo, hi in zip(bounds, bounds[1:])]


def init_ssm_lm(cfg: ModelConfig, key) -> Dict:
    """SSM: one stack of Mamba2 layers.  Hybrid: one stack per group of
    ``hybrid_groups``, made apart at init so that no program slices a
    group out of a whole-depth stack; the shared blocks; one adapter and
    linear per use."""
    ke, kl, ka, ku = jax.random.split(key, 4)
    p = {"embed": init_embedding(cfg, ke), "final_norm": init_norm(cfg)}
    block = lambda k: init_ssm_block(cfg, k)
    if cfg.family == "ssm":
        p["layers"] = stack_init(block, kl, cfg.num_layers)
        return p
    groups = hybrid_groups(cfg)
    p["layers"] = [stack_init(block, k, hi - lo) for (lo, hi, _), k in
                   zip(groups, jax.random.split(kl, len(groups)))]
    p["shared"] = [init_shared_block(cfg, k) for k in
                   jax.random.split(ka, cfg.num_mem_blocks)]
    p["uses"] = [init_hybrid_use(cfg, k) for k in
                 jax.random.split(ku, len(cfg.hybrid_layer_ids))]
    return p


def ssm_backbone_forward(params, x, cfg: ModelConfig, positions, *,
                         return_cache: bool = False, remat: bool = False,
                         window: Optional[int] = None):
    """SSM: one scan.  Hybrid (Zamba2): per group, the shared block (block
    use % num_mem_blocks, over [h, x0]) and then the group's scan, whose
    first layer takes the block's output."""
    if cfg.family == "ssm":
        x, c = _ssm_scan(params["layers"], x, cfg,
                         return_cache=return_cache, remat=remat)
        x = apply_norm(params["final_norm"], x, cfg)
        return x, ({"ssm": c} if return_cache else None), 0.0
    x0 = x
    ssm_caches, kv = [], []
    for (_, _, use), layers in zip(hybrid_groups(cfg), params["layers"]):
        t = None
        if use is not None:
            t, kc = apply_shared_block(
                params["shared"][use % cfg.num_mem_blocks],
                params["uses"][use], x, x0, cfg, positions,
                return_cache=return_cache)
            kv.append(kc)
        x, c = _ssm_scan(layers, x, cfg, t=t, return_cache=return_cache,
                         remat=remat)
        ssm_caches.append(c)
    x = apply_norm(params["final_norm"], x, cfg)
    if not return_cache:
        return x, None, 0.0
    return x, {"ssm": ssm_caches,
               "attn": jax.tree.map(lambda *a: jnp.stack(a), *kv)}, 0.0


def ssm_backbone_decode(params, x, cfg: ModelConfig, cache, cur_pos, *,
                        window: Optional[int] = None):
    """One decode step.  Each group's state is updated in place in its
    scan; the shared blocks only read their uses' KV caches, and one
    write after all groups puts every use's new rows in."""
    if cfg.family == "ssm":
        x, st = _ssm_scan_decode(params["layers"], x, cfg, cache["ssm"])
        return apply_norm(params["final_norm"], x, cfg), {"ssm": st}
    x0 = x
    states, rows = [], []
    for (_, _, use), layers, st in zip(hybrid_groups(cfg), params["layers"],
                                       cache["ssm"]):
        t = None
        if use is not None:
            kv = jax.tree.map(lambda a: a[use], cache["attn"])
            t, r = apply_shared_block(
                params["shared"][use % cfg.num_mem_blocks],
                params["uses"][use], x, x0, cfg, cur_pos, cache=kv)
            rows.append(r)
        x, st = _ssm_scan_decode(layers, x, cfg, st, t=t)
        states.append(st)
    new_cache = {"ssm": states, "attn": attn.write_decode_rows(
        cache["attn"], jax.tree.map(lambda *a: jnp.stack(a), *rows),
        cur_pos, per_slot=True)}
    return apply_norm(params["final_norm"], x, cfg), new_cache

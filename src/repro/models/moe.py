"""Mixture-of-Experts FFN: top-k router + capacity-bounded scatter dispatch.

Dispatch avoids the O(T x E x C) one-hot tensors used by classic Switch
implementations: tokens are ranked within their expert group via a single
argsort, scattered into an (E*C+1, D) buffer (last row = overflow dump),
batch-matmul'ed against stacked expert weights, and gathered back with
their gate weights.  FLOPs are therefore proportional to *active* params
(E x C x d x f with C ~= T*k/E*cf), which the roofline analysis relies on.

Sharding: expert dim -> "expert" (model axis); token dim -> "batch"
(data axes).  XLA inserts the all-to-all-equivalent collectives at the
scatter/gather boundaries.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import P, shard
from repro.models.layers import apply_mlp, dense_init, init_mlp


def init_moe(cfg: ModelConfig, key) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), ("embed", None),
                             dtype=jnp.float32),
        "wi": dense_init(ks[1], (E, d, f), ("expert", "embed", "expert_mlp"),
                         in_axis=1, dtype=dt),
        "wg": dense_init(ks[2], (E, d, f), ("expert", "embed", "expert_mlp"),
                         in_axis=1, dtype=dt),
        "wo": dense_init(ks[3], (E, f, d), ("expert", "expert_mlp", "embed"),
                         in_axis=1, dtype=dt),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4],
                               d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
    return p


def apply_moe(params, x, cfg: ModelConfig, decode: bool = False):
    """x: (B, S, D) -> (y, aux_loss).

    decode=True uses the per-token expert-weight *gather* path: no capacity
    dropping and HBM traffic proportional to top-k expert weights — the
    memory-bound regime real MoE decode lives in.  Training/prefill uses
    capacity-bounded scatter dispatch (compute-bound regime).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.moe_top_k
    T = B * S
    xt = x.reshape(T, D)
    xt = shard(xt, "batch", "embed_act")

    logits = (xt.astype(jnp.float32) @ params["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, K)                        # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    if decode:
        y = _gather_experts(params, xt, gates, eidx, cfg)
        if cfg.num_shared_experts:
            y = y + apply_mlp(params["shared"], xt[:, None, :], cfg)[:, 0, :]
        return y.reshape(B, S, D), 0.0

    # ---- load-balance aux loss (Switch/DeepSeek style) ---------------------
    f_e = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0)
    f_e = f_e / (T * K)
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e) * cfg.router_aux_coef

    # ---- capacity-bounded dispatch -----------------------------------------
    C = max(1, int(math.ceil(T * K / E * cfg.capacity_factor)))
    e_flat = eidx.reshape(-1)                                    # (T*K,)
    g_flat = gates.reshape(-1)
    order = jnp.argsort(e_flat)                                  # stable
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    group_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(T * K, dtype=jnp.int32) - group_start[e_flat[order]]
    pos = jnp.zeros((T * K,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < C
    dest = jnp.where(keep, e_flat * C + pos, E * C)              # dump row

    buf = jnp.zeros((E * C + 1, D), x.dtype).at[dest].add(
        xt[jnp.repeat(jnp.arange(T), K)])
    eb = buf[:E * C].reshape(E, C, D)
    eb = shard(eb, "expert", "capacity", "embed_act")

    # ---- expert FFN (batched over experts) ---------------------------------
    h = jnp.einsum("ecd,edf->ecf", eb, params["wi"])
    if cfg.act in ("silu", "geglu"):
        gact = jnp.einsum("ecd,edf->ecf", eb, params["wg"])
        gact = jax.nn.silu(gact) if cfg.act == "silu" else jax.nn.gelu(gact)
        h = gact * h
    else:
        h = jax.nn.gelu(h)
    h = shard(h, "expert", "capacity", "expert_mlp")
    eo = jnp.einsum("ecf,efd->ecd", h, params["wo"])
    eo = shard(eo, "expert", "capacity", "embed_act")

    # ---- combine -------------------------------------------------------------
    out_rows = jnp.concatenate(
        [eo.reshape(E * C, D), jnp.zeros((1, D), eo.dtype)], axis=0)[dest]
    out_rows = out_rows * (g_flat * keep)[:, None].astype(eo.dtype)
    y = out_rows.reshape(T, K, D).sum(axis=1)

    if cfg.num_shared_experts:
        y = y + apply_mlp(params["shared"], xt[:, None, :], cfg)[:, 0, :]
    return y.reshape(B, S, D), aux


def _gather_experts(params, xt, gates, eidx, cfg: ModelConfig):
    """Per-token expert weight gather (decode path).  xt: (T, D)."""
    wi = params["wi"][eidx]                                   # (T, K, d, f)
    wo = params["wo"][eidx]                                   # (T, K, f, d)
    h = jnp.einsum("td,tkdf->tkf", xt, wi)
    if cfg.act in ("silu", "geglu"):
        wg = params["wg"][eidx]
        g = jnp.einsum("td,tkdf->tkf", xt, wg)
        g = jax.nn.silu(g) if cfg.act == "silu" else jax.nn.gelu(g)
        h = g * h
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("tkf,tkfd->tkd", h, wo)
    return jnp.einsum("tkd,tk->td", out, gates.astype(out.dtype))

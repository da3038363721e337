"""Attention: GQA/MQA, MLA (DeepSeek), sliding-window ring KV cache.

Cache layout (uniform for full + windowed attention)::

    {"k": (B, W, Hkv, hd), "v": (B, W, Hkv, hd), "pos": (B, W) int32}

``pos[b, s]`` is the absolute position held in slot ``s`` (-1 = empty).
For full attention W == max_seq and slot index == position; for a sliding
window of size w, W == w and slot index == position % w (ring buffer).
Keys are stored *after* RoPE, so the mask is the only position-dependent
piece at read time.

MLA caches the compressed latent instead::

    {"ckv": (B, W, kv_lora), "krope": (B, W, rope_dim), "pos": (B, W)}

Prefill uses a q-block lazy-flash (lax.scan over query blocks) so the
(S, T) score matrix is never fully materialized; decode uses the absorbed
MLA form / direct GQA reduction.  A decode step only reads each layer's
cache: every layer returns its new token's rows, and the caller writes
all layers' rows into the stacked cache at once (``write_decode_rows``),
so the cache is neither copied nor re-emitted by the layer scan.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import P, shard
from repro.models import flags
from repro.models.layers import apply_norm, apply_rope, dense_init, init_norm

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, d_in: Optional[int] = None) -> Dict:
    """``d_in``: width of the attention's input where it differs from
    d_model, its output's (Zamba2's shared block attends over [h, x0])."""
    dt = jnp.dtype(cfg.dtype)
    d, hd = cfg.d_model, cfg.head_dim
    di = d_in or d
    if cfg.use_mla:
        m = cfg.mla
        ks = jax.random.split(key, 6)
        qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
        p = {
            "wq_a": dense_init(ks[0], (d, m.q_lora_rank), ("embed", "lora"), dtype=dt),
            "wq_b": dense_init(ks[1], (m.q_lora_rank, cfg.num_heads * qk_head),
                               ("lora", "qkv"), dtype=dt),
            "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                                ("embed", "lora"), dtype=dt),
            "wk_b": dense_init(ks[3], (m.kv_lora_rank,
                                       cfg.num_heads * m.qk_nope_head_dim),
                               ("lora", "qkv"), dtype=dt),
            "wv_b": dense_init(ks[4], (m.kv_lora_rank,
                                       cfg.num_heads * m.v_head_dim),
                               ("lora", "qkv"), dtype=dt),
            "wo": dense_init(ks[5], (cfg.num_heads * m.v_head_dim, d),
                             ("qkv", "embed"), dtype=dt),
            "q_norm": {"scale": P(jnp.ones((m.q_lora_rank,), jnp.float32),
                                  (None,))},
            "kv_norm": {"scale": P(jnp.ones((m.kv_lora_rank,), jnp.float32),
                                   (None,))},
        }
        return p
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (di, cfg.num_heads * hd), ("embed", "qkv"), dtype=dt),
        "wk": dense_init(ks[1], (di, cfg.num_kv_heads * hd), ("embed", "qkv"), dtype=dt),
        "wv": dense_init(ks[2], (di, cfg.num_kv_heads * hd), ("embed", "qkv"), dtype=dt),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), ("qkv", "embed"), dtype=dt),
    }
    if cfg.use_qkv_bias:
        p["bq"] = P(jnp.zeros((cfg.num_heads * hd,), dt), ("qkv",))
        p["bk"] = P(jnp.zeros((cfg.num_kv_heads * hd,), dt), ("qkv",))
        p["bv"] = P(jnp.zeros((cfg.num_kv_heads * hd,), dt), ("qkv",))
    return p


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               window: Optional[int] = None) -> Dict:
    """Single-layer cache (the model stacks these along a layer axis)."""
    w = window or (cfg.sliding_window or max_seq)
    w = min(w, max_seq)
    dt = jnp.dtype(cfg.dtype)
    if cfg.use_mla:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, w, m.kv_lora_rank), dt),
            "krope": jnp.zeros((batch, w, m.qk_rope_head_dim), dt),
            "pos": jnp.full((batch, w), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dt),
        "pos": jnp.full((batch, w), -1, jnp.int32),
    }


def cache_logical_axes(cfg: ModelConfig, long_context: bool = False) -> Dict:
    """Logical axes for the cache (kv_seq shardable for long-context)."""
    seq = "kv_seq"
    if cfg.use_mla:
        return {"ckv": ("batch", seq, "lora"),
                "krope": ("batch", seq, None),
                "pos": ("batch", seq)}
    return {"k": ("batch", seq, "kv_heads", "head_dim"),
            "v": ("batch", seq, "kv_heads", "head_dim"),
            "pos": ("batch", seq)}


# --------------------------------------------------------------------------
# Core attention math
# --------------------------------------------------------------------------

def _attend(q, k, v, mask, scale):
    """q:(B,S,H,hd) k/v:(B,T,Hkv,hd) mask:(B,S,T) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, hd)
    if flags.ATTN_BF16_STREAM:
        # bf16 operands, fp32 accumulation: halves K/V HBM traffic and
        # skips the fp32 materialization (see EXPERIMENTS.md §Perf)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", w, v,
                         preferred_element_type=jnp.float32)
    else:
        qg = qg.astype(jnp.float32)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                            k.astype(jnp.float32)) * scale
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def blockwise_attention(q, k, v, q_positions, k_positions, *,
                        window: int = 0, scale: float, block_q: int = 1024):
    """Causal (optionally windowed) attention scanning over query blocks.

    Never materializes the full (S, T) score tensor: peak score memory is
    (B, H, block_q, T).  q_positions/k_positions are absolute positions;
    k slots with position < 0 are masked out.
    """
    B, S, H, hd = q.shape
    if flags.PROBE_BLOCK_Q:
        block_q = flags.PROBE_BLOCK_Q
    bq = min(block_q, S)
    pad = (-S) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
    nblk = q.shape[1] // bq
    qb = q.reshape(B, nblk, bq, H, hd).transpose(1, 0, 2, 3, 4)
    pb = q_positions.reshape(B, nblk, bq).transpose(1, 0, 2)

    def step(_, inp):
        qi, pi = inp                          # (B,bq,H,hd), (B,bq)
        mask = (k_positions[:, None, :] <= pi[:, :, None])
        mask &= (k_positions[:, None, :] >= 0) & (pi[:, :, None] >= 0)
        if window:
            mask &= (pi[:, :, None] - k_positions[:, None, :]) < window
        return None, _attend(qi, k, v, mask, scale)

    _, out = jax.lax.scan(step, None, (qb, pb),
                          unroll=flags.scan_unroll())
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nblk * bq, H, v.shape[-1])
    return out[:, :S]


# --------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# --------------------------------------------------------------------------

def attention_forward(params, x, cfg: ModelConfig, positions,
                      *, causal: bool = True, return_cache: bool = False,
                      window: Optional[int] = None,
                      kv_x: Optional[jnp.ndarray] = None):
    """x: (B, S, D).  kv_x != None => cross-attention (no causal mask)."""
    if cfg.use_mla:
        return _mla_forward(params, x, cfg, positions,
                            return_cache=return_cache)
    B, S, _ = x.shape
    hd = cfg.head_dim
    src = x if kv_x is None else kv_x
    T = src.shape[1]
    q = x @ params["wq"]
    k = src @ params["wk"]
    v = src @ params["wv"]
    if cfg.use_qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, T, cfg.num_kv_heads, hd)
    v = v.reshape(B, T, cfg.num_kv_heads, hd)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")

    if cfg.pos_emb == "rope" and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    scale = cfg.softmax_scale
    if causal and kv_x is None:
        kpos = positions
        out = blockwise_attention(q, k, v, positions, kpos,
                                  window=window or cfg.sliding_window,
                                  scale=scale)
    else:  # bidirectional (encoder) or cross attention
        kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        mask = jnp.ones((B, S, T), bool)
        out = _attend(q, k, v, mask, scale)

    y = out.reshape(B, S, cfg.num_heads * hd) @ params["wo"]
    y = shard(y, "batch", "seq", "embed_act")
    if not return_cache:
        return y, None
    return y, {"k": k, "v": v, "pos": positions.astype(jnp.int32)}


def _mla_forward(params, x, cfg: ModelConfig, positions, *,
                 return_cache: bool):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q_lat = apply_norm(params["q_norm"], x @ params["wq_a"], cfg)
    q = (q_lat @ params["wq_b"]).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ params["wkv_a"]
    ckv, k_rope = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    ckv = apply_norm(params["kv_norm"], ckv, cfg)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]

    k_nope = (ckv @ params["wk_b"]).reshape(B, S, H, nope)
    v = (ckv @ params["wv_b"]).reshape(B, S, H, vd)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rope_d))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = 1.0 / math.sqrt(nope + rope_d)
    out = blockwise_attention(q_full, k, v, positions, positions,
                              window=0, scale=scale)
    y = out.reshape(B, S, H * vd) @ params["wo"]
    y = shard(y, "batch", "seq", "embed_act")
    if not return_cache:
        return y, None
    return y, {"ckv": ckv, "krope": k_rope, "pos": positions.astype(jnp.int32)}


# --------------------------------------------------------------------------
# Single-token decode
# --------------------------------------------------------------------------

def _decode_mask(kpos, cur_pos, window: int = 0):
    """Which cached positions a decoding token attends, besides itself.

    kpos: (B, W) positions held in the cache before this step; cur_pos:
    (B,).  The new token's own row is not in the cache yet, and its slot
    (cur_pos % W) may hold a stale row with pos == cur_pos from an earlier,
    longer request, hence ``<``.  In a ring (W == window) that slot holds
    cur_pos - W, which the window drops."""
    mask = (kpos >= 0) & (kpos < cur_pos[:, None])
    if window:
        mask &= (cur_pos[:, None] - kpos) < window
    return mask


def _decode_weights(scores, score_new, mask):
    """Softmax over the cached positions' scores joined with the new
    token's own.  scores: (..., W) f32; score_new: (...); mask broadcast
    to scores.  Returns (w_cached (..., W), w_new (...))."""
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(
        jnp.concatenate([scores, score_new[..., None]], axis=-1), axis=-1)
    return w[..., :-1], w[..., -1]


def _attend_decode(q, k, v, k_new, v_new, mask, scale):
    """One query per row over the cache plus the row's own new key/value.

    q: (B,1,H,hd); k/v: (B,W,Hkv,hd) as stored; k_new/v_new: (B,Hkv,hd);
    mask: (B,W).  QK and AV read the cache in its stored dtype with float32
    accumulation (MXU dots); scores and softmax are float32."""
    B, _, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, Hkv, H // Hkv, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    score_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new,
                           preferred_element_type=jnp.float32) * scale
    w, w_new = _decode_weights(scores, score_new, mask[:, None, None, :])
    out = jnp.einsum("bkgt,btkd->bkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out += w_new[..., None] * v_new[:, :, None, :].astype(jnp.float32)
    return out.reshape(B, 1, H, v.shape[-1]).astype(q.dtype)


def write_decode_rows(cache: Dict, rows: Dict, cur_pos, *,
                      per_slot: bool = False) -> Dict:
    """Write each layer's new-token rows into a stacked decode cache.

    cache leaves are (L, B, W, ...); rows hold the same keys but "pos",
    each (L, B, ...) in the cache's dtype.  Row b goes to slot
    cur_pos[b] % W of every layer, and pos there becomes cur_pos[b];
    nothing else changes.  With the cache donated the update is in place:
    one scatter over layer, row and slot, so the window it writes is the
    cache's minor dimensions.  ``per_slot`` writes instead with one
    dynamic-update-slice per row b, which stays in place whatever the
    cache's device layout: a TPU lays a cache with head_dim 224 out with
    the positions minor, and there a scatter re-lays the whole cache."""
    L, B, W = cache["pos"].shape
    slot = jnp.mod(cur_pos, W)
    pos = jnp.broadcast_to(cur_pos.astype(jnp.int32), (L, B))
    new = dict(rows, pos=pos)
    if per_slot:
        def put(c, r):
            for b in range(B):
                start = (0, b, slot[b]) + (0,) * (c.ndim - 3)
                c = jax.lax.dynamic_update_slice(
                    c, r[:, b:b + 1, None], start)
            return c
    elif flags.WHERE_CACHE_UPDATE:
        sel = (jnp.arange(W, dtype=jnp.int32)[None, :]
               == slot[:, None])                         # (B, W)

        def put(c, r):
            s = sel.reshape((1, B, W) + (1,) * (c.ndim - 3))
            return jnp.where(s, r[:, :, None], c)
    else:
        lidx = jnp.arange(L)[:, None]
        bidx = jnp.arange(B)[None, :]

        def put(c, r):
            return c.at[lidx, bidx, slot[None, :]].set(r)
    return {name: put(c, new[name]) for name, c in cache.items()}


def attention_decode(params, x, cfg: ModelConfig, cache: Dict,
                     cur_pos: jnp.ndarray,
                     window: Optional[int] = None):
    """x: (B, 1, D); cur_pos: (B,) absolute position of the new token.

    ``cache`` (one layer) is only read.  Returns (y, rows): the new
    token's cache rows, {"k", "v"} each (B, Hkv, hd), for
    ``write_decode_rows`` to put at slot cur_pos % W once all layers ran.
    """
    if cfg.use_mla:
        return _mla_decode(params, x, cfg, cache, cur_pos)
    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ params["wq"])
    k = (x @ params["wk"])
    v = (x @ params["wv"])
    if cfg.use_qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    # the projections keep their row layout: without the barrier XLA lays
    # q out head-major for the attention and so copies wq, wk and wv into
    # that layout in every layer of every decode (on a TPU v5e)
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    q = q.reshape(B, 1, cfg.num_heads, hd)
    k = k.reshape(B, 1, cfg.num_kv_heads, hd)
    v = v.reshape(B, 1, cfg.num_kv_heads, hd)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, cur_pos[:, None], cfg.rope_theta)
        k = apply_rope(k, cur_pos[:, None], cfg.rope_theta)

    rows = {"k": k[:, 0].astype(cache["k"].dtype),
            "v": v[:, 0].astype(cache["v"].dtype)}
    mask = _decode_mask(cache["pos"], cur_pos, window or cfg.sliding_window)
    out = _attend_decode(q, cache["k"], cache["v"], rows["k"], rows["v"],
                         mask, cfg.softmax_scale)
    y = out.reshape(B, 1, cfg.num_heads * hd) @ params["wo"]
    return shard(y, "batch", None, "embed_act"), rows


def cross_attention_decode(params, x, cfg: ModelConfig, cross_cache: Dict):
    """Decoder cross-attn against a fixed, precomputed encoder KV cache."""
    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ params["wq"])
    if cfg.use_qkv_bias:
        q = q + params["bq"]
    q = q.reshape(B, 1, cfg.num_heads, hd)
    mask = jnp.ones((B, 1, cross_cache["k"].shape[1]), bool)
    out = _attend(q, cross_cache["k"], cross_cache["v"], mask,
                  cfg.softmax_scale)
    y = out.reshape(B, 1, cfg.num_heads * hd) @ params["wo"]
    return shard(y, "batch", None, "embed_act")


def _mla_decode(params, x, cfg: ModelConfig, cache: Dict, cur_pos):
    """Absorbed-matrix MLA decode: attention runs in the latent space.

    Same contract as ``attention_decode``: the cache is only read, and the
    new token's rows {"ckv": (B, lora), "krope": (B, rope_dim)} return."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    lora = m.kv_lora_rank

    q_lat = apply_norm(params["q_norm"], x @ params["wq_a"], cfg)
    q = (q_lat @ params["wq_b"]).reshape(B, 1, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, cur_pos[:, None], cfg.rope_theta)

    kv = x @ params["wkv_a"]
    ckv_new = apply_norm(params["kv_norm"], kv[..., :lora], cfg)
    krope_new = apply_rope(kv[..., None, lora:], cur_pos[:, None],
                           cfg.rope_theta)[:, :, 0, :]
    rows = {"ckv": ckv_new[:, 0].astype(cache["ckv"].dtype),
            "krope": krope_new[:, 0].astype(cache["krope"].dtype)}
    ckv_n = rows["ckv"][:, None].astype(jnp.float32)        # (B,1,lora)
    krope_n = rows["krope"][:, None].astype(jnp.float32)

    # absorb W_uk into q: (B,1,H,nope) x (lora, H, nope) -> (B,1,H,lora)
    wk_b = params["wk_b"].reshape(lora, H, nope)
    q_abs = jnp.einsum("bshn,lhn->bshl", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    q_rope = q_rope.astype(jnp.float32)
    ckv = cache["ckv"].astype(jnp.float32)
    scores = jnp.einsum("bshl,btl->bhst", q_abs, ckv)
    scores += jnp.einsum("bshr,btr->bhst", q_rope,
                         cache["krope"].astype(jnp.float32))
    score_new = (jnp.einsum("bshl,bsl->bhs", q_abs, ckv_n)
                 + jnp.einsum("bshr,bsr->bhs", q_rope, krope_n))
    scale = 1.0 / math.sqrt(nope + rope_d)
    mask = _decode_mask(cache["pos"], cur_pos)
    w, w_new = _decode_weights(scores * scale, score_new * scale,
                               mask[:, None, None, :])
    ctx = (jnp.einsum("bhst,btl->bshl", w, ckv)
           + jnp.einsum("bhs,bsl->bshl", w_new, ckv_n))
    wv_b = params["wv_b"].reshape(lora, H, vd)
    out = jnp.einsum("bshl,lhv->bshv", ctx, wv_b.astype(jnp.float32))
    y = out.reshape(B, 1, H * vd).astype(x.dtype) @ params["wo"]
    return shard(y, "batch", None, "embed_act"), rows

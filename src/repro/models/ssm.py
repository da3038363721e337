"""Mamba2 (SSD — state-space duality) mixer, pure JAX.

Training/prefill uses the chunked SSD algorithm of arXiv:2405.21060:
quadratic attention-like computation within chunks, linear recurrence in
chunk states across chunks (``jax.lax.scan``; cross-chunk Pallas kernel in
``repro.kernels.ssd_scan``).  Decode is the O(1) recurrent step with a
(conv, ssm-state) cache.

Shapes: x (B, L, H, P) with H = d_inner/headdim heads; B/C projections are
(B, L, G, N): G groups of heads share them (Mamba2's ``ngroups``; head h
is in group h // (H/G)); state size N.  The gated output norm is an
RMSNorm per group.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import P, shard
from repro.models import flags
from repro.models.layers import dense_init

CONV_WIDTH = 4


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def init_ssm(cfg: ModelConfig, key) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    di = cfg.ssm_d_inner
    H = cfg.ssm_nheads
    conv_ch = cfg.ssm_conv_dim
    ks = jax.random.split(key, 4)
    dt_init = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32)
                      * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inv-softplus
    return {
        # order: [z(di), x(di), B(G*N), C(G*N), dt(H)]
        "in_proj": dense_init(ks[0], (d, di + conv_ch + H),
                              ("embed", "ssm_inner"), dtype=dt),
        "conv_w": P(jax.random.normal(ks[3], (CONV_WIDTH, conv_ch),
                                      jnp.float32).astype(dt) * 0.2,
                    ("conv", "ssm_inner")),
        "conv_b": P(jnp.zeros((conv_ch,), dt), ("ssm_inner",)),
        "A_log": P(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                   ("ssm_heads",)),
        "D": P(jnp.ones((H,), jnp.float32), ("ssm_heads",)),
        "dt_bias": P(dt_bias, ("ssm_heads",)),
        "gate_norm": P(jnp.ones((di,), jnp.float32), ("ssm_inner",)),
        "out_proj": dense_init(ks[1], (di, d), ("ssm_inner", "embed"),
                               dtype=dt),
    }


def init_ssm_cache(cfg: ModelConfig, batch: int) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    N, H, Pd = cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    return {
        "conv": jnp.zeros((batch, CONV_WIDTH - 1, cfg.ssm_conv_dim), dt),
        "ssm": jnp.zeros((batch, H, Pd, N), jnp.float32),
    }


def ssm_cache_logical_axes(cfg: ModelConfig) -> Dict:
    return {"conv": ("batch", None, "ssm_inner"),
            "ssm": ("batch", "ssm_heads", None, "state")}


# --------------------------------------------------------------------------
# SSD core
# --------------------------------------------------------------------------

def _segsum(a):
    """a: (..., cl, h) -> (..., h, cl, cl) lower-tri segment sums."""
    cl = a.shape[-2]
    ah = jnp.moveaxis(a, -1, -2)                       # (..., h, cl)
    cs = jnp.cumsum(ah, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]          # sum_(j..i]
    mask = jnp.tril(jnp.ones((cl, cl), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int,
                initial_state: Optional[jnp.ndarray] = None,
                use_kernel: bool = False):
    """Chunked SSD.

    x: (b, l, h, p) fp32; dt: (b, l, h) fp32 (post-softplus);
    A: (h,) fp32 (negative); Bm/Cm: (b, l, g, n) fp32, g dividing h.
    Returns y (b, l, h, p), final_state (b, h, p, n).
    """
    b, l, h, p = x.shape
    g, n = Bm.shape[-2:]
    e = h // g                                         # heads per group
    pad = (-l) % chunk
    if pad:
        z = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, Bm, Cm = z(x), z(dt), z(Bm), z(Cm)
    L = x.shape[1]
    c = L // chunk
    xr = x.reshape(b, c, chunk, g, e, p)
    dtr = dt.reshape(b, c, chunk, g, e)
    Br = Bm.reshape(b, c, chunk, g, n)
    Cr = Cm.reshape(b, c, chunk, g, n)

    dA = dtr * A.reshape(g, e)                         # (b,c,cl,g,e)
    dA_cs = jnp.cumsum(dA, axis=2)

    # ---- intra-chunk (quadratic within chunk) -------------------------------
    Lmat = jnp.exp(_segsum(dA.reshape(b, c, chunk, h))
                   ).reshape(b, c, g, e, chunk, chunk)  # (b,c,g,e,z,l)
    G = jnp.einsum("bczgn,bclgn->bcgzl", Cr, Br)       # (b,c,g,cl_q,cl_k)
    M = G[:, :, :, None] * Lmat                        # (b,c,g,e,z,l)
    y_diag = jnp.einsum("bcgezl,bclge,bclgep->bczgep", M, dtr, xr)

    # ---- chunk states --------------------------------------------------------
    decay_states = jnp.exp(dA_cs[:, :, -1:] - dA_cs)          # (b,c,cl,g,e)
    states = jnp.einsum("bclgn,bclge,bclgep->bcgepn",
                        Br, decay_states * dtr, xr
                        ).reshape(b, c, h, p, n)              # (b,c,h,p,n)

    # ---- inter-chunk recurrence ---------------------------------------------
    chunk_decay = jnp.exp(dA_cs[:, :, -1]).reshape(b, c, h)   # (b,c,h)
    s0 = (jnp.zeros((b, h, p, n), jnp.float32)
          if initial_state is None else initial_state)
    if use_kernel:
        from repro.kernels import ops as _kops
        prev_states, final = _kops.ssd_state_scan(states, chunk_decay, s0)
    else:
        def step(carry, inp):
            st, dec = inp
            new = carry * dec[:, :, None, None] + st
            return new, carry
        final, prev_states = jax.lax.scan(
            step, s0, (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)),
            unroll=flags.scan_unroll())
        prev_states = prev_states.swapaxes(0, 1)              # (b,c,h,p,n)

    # ---- chunk-start contribution -------------------------------------------
    state_decay = jnp.exp(dA_cs)                              # (b,c,cl,g,e)
    y_off = jnp.einsum("bczgn,bcgepn,bczge->bczgep", Cr,
                       prev_states.reshape(b, c, g, e, p, n), state_decay)

    y = (y_diag + y_off).reshape(b, L, h, p)[:, :l]
    return y, final


def ssd_step(state, x_t, dt_t, A, B_t, C_t):
    """One recurrent step.  state: (b,h,p,n); x_t: (b,h,p); dt_t: (b,h);
    B_t/C_t: (b,g,n).  Returns (new_state, y_t).  B and C are repeated
    to the heads rather than the state split by group: the state keeps
    its (b,h,p,n) shape, so its device layout is never changed.  y is
    read from the old state, y = dA (s C) + dt x (B C), the same sum as
    the new state's s' C: the new state is then an elementwise function
    of the old alone, which XLA writes in place over a state carried in a
    layer scan, without a temporary of the state's size."""
    e = state.shape[1] // B_t.shape[1]
    Bh, Ch = jnp.repeat(B_t, e, axis=1), jnp.repeat(C_t, e, axis=1)
    dA = jnp.exp(dt_t * A)                                    # (b,h)
    dtx = dt_t[:, :, None] * x_t                              # (b,h,p)
    new_state = state * dA[:, :, None, None] \
        + dtx[..., None] * Bh[:, :, None, :]
    y = (dA[:, :, None] * jnp.einsum("bhpn,bhn->bhp", state, Ch)
         + dtx * jnp.sum(Bh * Ch, axis=-1)[:, :, None])
    return new_state, y


# --------------------------------------------------------------------------
# Full mixer (in_proj -> conv -> SSD -> gate -> out_proj)
# --------------------------------------------------------------------------

def _split_proj(cfg: ModelConfig, zxbcdt):
    """[z, x|B|C (the conv's input), dt] along the last axis."""
    di = cfg.ssm_d_inner
    conv = cfg.ssm_conv_dim
    return (zxbcdt[..., :di], zxbcdt[..., di:di + conv],
            zxbcdt[..., di + conv:])


def _split_conv(cfg: ModelConfig, xbc):
    """The conv's output (..., conv_dim) as x (..., d_inner) and B, C
    (..., G, N), in float32."""
    di, G, N = cfg.ssm_d_inner, cfg.ssm_ngroups, cfg.ssm_state
    xbc = xbc.astype(jnp.float32)
    lead = xbc.shape[:-1]
    return (xbc[..., :di], xbc[..., di:di + G * N].reshape(*lead, G, N),
            xbc[..., di + G * N:].reshape(*lead, G, N))


def _causal_conv(xc, w, b):
    """Depthwise causal conv.  xc: (B, L, C); w: (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(xc, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + xc.shape[1], :] * w[i] for i in range(W))
    return jax.nn.silu(out + b)


def _gated_out(cfg, params, y, z, x_conv):
    """D skip, y * silu(z), an RMSNorm over each group's channels, then
    out_proj."""
    di, G = cfg.ssm_d_inner, cfg.ssm_ngroups
    y = y + params["D"][:, None] * x_conv.reshape(y.shape)
    yf = y.reshape(*y.shape[:-2], di)
    yf = yf * jax.nn.silu(z.astype(jnp.float32))
    yg = yf.reshape(*yf.shape[:-1], G, di // G)
    ms = jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
    yf = (yg * jax.lax.rsqrt(ms + cfg.norm_eps)).reshape(yf.shape)
    yf = yf * params["gate_norm"]
    return yf.astype(jnp.dtype(cfg.dtype)) @ params["out_proj"]


def ssm_forward(params, x, cfg: ModelConfig,
                initial_state: Optional[Dict] = None,
                return_cache: bool = False):
    """x: (B, L, D) -> (y, cache|None).  Full-sequence (train/prefill)."""
    Bsz, L, _ = x.shape
    H, Pd = cfg.ssm_nheads, cfg.ssm_headdim
    with jax.named_scope("mamba2.in_proj"):
        zxbcdt = x @ params["in_proj"]
        z, pre, dtl = _split_proj(cfg, zxbcdt)
    with jax.named_scope("mamba2.ssd"):
        xc = shard(pre, "batch", "seq", "ssm_inner")
        xc = _causal_conv(xc, params["conv_w"], params["conv_b"])
        xs, Bm, Cm = _split_conv(cfg, xc)
        dt = jax.nn.softplus(dtl.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["A_log"])
        xh = xs.reshape(Bsz, L, H, Pd)
        xh = shard(xh, "batch", "seq", "ssm_heads", None)
        y, final = ssd_chunked(
            xh, dt, A, Bm, Cm, cfg.ssm_chunk,
            initial_state=None if initial_state is None
            else initial_state["ssm"])
    with jax.named_scope("mamba2.out"):
        out = _gated_out(cfg, params, y, z, xs)
        out = shard(out, "batch", "seq", "embed_act")
    if not return_cache:
        return out, None
    # conv cache = last (W-1) *pre-activation* conv inputs
    if L >= CONV_WIDTH - 1:
        conv_cache = pre[:, -(CONV_WIDTH - 1):, :]
    else:
        conv_cache = jnp.pad(pre, ((0, 0), (CONV_WIDTH - 1 - L, 0), (0, 0)))
    return out, {"conv": conv_cache.astype(jnp.dtype(cfg.dtype)),
                 "ssm": final}


def ssm_decode(params, x, cfg: ModelConfig, cache: Dict):
    """x: (B, 1, D) -> (y, new_cache)."""
    Bsz = x.shape[0]
    H, Pd = cfg.ssm_nheads, cfg.ssm_headdim
    with jax.named_scope("mamba2.in_proj"):
        zxbcdt = x @ params["in_proj"]
        z, xc_new, dtl = _split_proj(cfg, zxbcdt)
    with jax.named_scope("mamba2.ssd"):
        # conv over [cache, new]
        window = jnp.concatenate(
            [cache["conv"], xc_new.astype(cache["conv"].dtype)],
            axis=1)                                           # (B, W, C)
        conv_out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                              params["conv_w"].astype(jnp.float32))
        conv_out = jax.nn.silu(
            conv_out + params["conv_b"].astype(jnp.float32))
        xs, Bm, Cm = _split_conv(cfg, conv_out)
        dt = jax.nn.softplus(dtl[:, 0].astype(jnp.float32)
                             + params["dt_bias"])
        A = -jnp.exp(params["A_log"])
        new_state, y = ssd_step(cache["ssm"], xs.reshape(Bsz, H, Pd), dt, A,
                                Bm, Cm)
    with jax.named_scope("mamba2.out"):
        out = _gated_out(cfg, params, y[:, None], z, xs[:, None, :])
    new_cache = {"conv": window[:, 1:], "ssm": new_state}
    return out, new_cache

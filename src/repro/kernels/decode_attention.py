"""Flash-decode attention — Pallas TPU kernel.

One new query token attends over a long KV cache.  The cache's sequence
axis is split across the minor grid dimension; each step reduces a
(block_k x head_dim) tile with online softmax in VMEM scratch — the
TPU-idiomatic grid-reduction replacing a GPU kv-split + warp-shuffle
combine.  Ring-buffer (sliding-window) caches work unchanged because
masking is driven entirely by the per-slot position array.

Each row's current position is a scalar-prefetch operand (SMEM), and
slot positions travel as (B, 1, T) so their block is (1, 1, block_k):
the chip's tiling rule wants the last two block dims divisible by
(8, 128) or equal to the array's, which a (1, block_k) slice of a
(B, T) array breaks for B > 1.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(cur_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, window, nk, g):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (g, hd) — the GQA group
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    kp = kpos_ref[0, 0]                           # (bk,)
    cur = cur_ref[pl.program_id(0)]               # scalar (SMEM)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (g, bk)
    mask = (kp >= 0) & (kp <= cur)
    if window:
        mask &= (cur - kp) < window
    s = jnp.where(mask[None, :], s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_new = acc_prev * alpha[:, None] + jax.lax.dot(p, v)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(ik == nk - 1)
    def _emit():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def decode_attention(q, k, v, k_pos, cur_pos, *, scale: float,
                     window: int = 0, block_k: int = 512,
                     interpret: Optional[bool] = None):
    """q: (B,H,hd); k/v: (B,Hkv,T,hd); k_pos: (B,T); cur_pos: (B,).

    Grid is (B, Hkv, nk): one step computes the whole GQA group g=H/Hkv
    for one kv-head so the K tile is loaded once per group, not per head.
    """
    B, H, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = H // Hkv
    bk = min(block_k, T)
    assert T % bk == 0, (T, bk)
    nk = T // bk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    qg = q.reshape(B, Hkv, g, hd)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               nk=nk, g=g)
    # index maps receive the scalar-prefetch ref as a trailing argument
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bk), lambda b, h, ik, cur: (b, 0, ik)),
                pl.BlockSpec((1, 1, g, hd),
                             lambda b, h, ik, cur: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, ik, cur: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, ik, cur: (b, h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda b, h, ik, cur: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        interpret=interpret,
    )(cur_pos.astype(jnp.int32), k_pos[:, None, :], qg, k, v)
    return out.reshape(B, H, hd)

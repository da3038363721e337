"""Prefill flash attention — Pallas TPU kernel.

Grid: (B, H, num_q_blocks, num_kv_blocks); the kv axis is minor, so the
online-softmax running state (m, l, acc) lives in VMEM scratch persisted
across kv iterations and the output tile is written on the last kv step.
Block shapes keep the MXU fed with (block_q x head_dim) @ (head_dim x
block_k) tiles; head_dim and block sizes should be multiples of 128 on
real hardware (validated here in interpret mode).

GQA is expressed in the K/V BlockSpec index_map (q head h reads kv head
h // group) — no KV replication in HBM.  Positions travel as (B, 1, S)
so each position block is (1, 1, block): the chip's tiling rule wants
the last two block dims divisible by (8, 128) or equal to the array's,
which a (1, block) slice of a (B, S) array breaks for B > 1.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, causal, window, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    qp = qpos_ref[0, 0]                           # (bq,)
    kp = kpos_ref[0, 0]                           # (bk,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = (kp[None, :] >= 0) & (qp[:, None] >= 0)
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window:
        mask &= (qp[:, None] - kp[None, :]) < window
    s = jnp.where(mask, s, NEG_INF)

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


def flash_attention(q, k, v, q_pos, k_pos, *, scale: float,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B,H,S,hd); k/v: (B,Hkv,T,hd); q_pos: (B,S); k_pos: (B,T)."""
    B, H, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = H // Hkv
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    nq, nk = S // bq, T // bk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq), lambda b, h, iq, ik: (b, 0, iq)),
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos[:, None, :], k_pos[:, None, :], q, k, v)

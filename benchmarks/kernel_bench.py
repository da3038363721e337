"""Instance-level kernel micro-bench (CPU interpret mode): us/call +
allclose check vs the jnp oracle.  Interpret-mode timings are NOT TPU
performance — the chip's rooflines are measured by bench/run.py; this
verifies the harness plumbing and correctness at bench shapes."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line
from repro.kernels import ops, ref


def _time(fn, *args, iters=3, **kw):
    fn(*args, **kw)  # compile/warm
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args, **kw))
    return (time.time() - t0) / iters * 1e6


def run(quick: bool = False):
    out = []
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, H, Hkv, S, hd = 1, 4, 2, 256, 64
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    us = _time(ops.flash_attention, q, k, v, pos, pos, scale=0.125)
    want = ref.flash_attention_ref(q, k, v, pos, pos, scale=0.125)
    got = ops.flash_attention(q, k, v, pos, pos, scale=0.125)
    err = float(jnp.max(jnp.abs(got - want)))
    out.append(csv_line("kernel.flash_attention.us_per_call", round(us, 1),
                        f"maxerr={err:.2e} (interpret mode)"))
    qd = q[:, :, 0, :]
    cur = jnp.asarray([S - 1], jnp.int32)
    us = _time(ops.decode_attention, qd, k, v, pos, cur, scale=0.125)
    got = ops.decode_attention(qd, k, v, pos, cur, scale=0.125)
    want = ref.decode_attention_ref(qd, k, v, pos, cur, scale=0.125)
    err = float(jnp.max(jnp.abs(got - want)))
    out.append(csv_line("kernel.decode_attention.us_per_call", round(us, 1),
                        f"maxerr={err:.2e}"))
    st = jax.random.normal(ks[0], (2, 16, 4, 16, 32), jnp.float32)
    dec = jax.random.uniform(ks[1], (2, 16, 4), jnp.float32)
    s0 = jnp.zeros((2, 4, 16, 32), jnp.float32)
    us = _time(ops.ssd_state_scan, st, dec, s0)
    p1, f1 = ops.ssd_state_scan(st, dec, s0)
    p2, f2 = ref.ssd_state_scan_ref(st, dec, s0)
    err = float(jnp.max(jnp.abs(p1 - p2)))
    out.append(csv_line("kernel.ssd_state_scan.us_per_call", round(us, 1),
                        f"maxerr={err:.2e}"))
    return out

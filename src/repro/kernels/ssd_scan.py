"""Mamba2 SSD cross-chunk state recurrence — Pallas TPU kernel.

The chunked SSD algorithm reduces the sequence dimension to ``c`` chunk
states of shape (head_dim, state); the remaining serial work is the
first-order recurrence  S_c = decay_c * S_{c-1} + states_c.  The grid
is (batch*head, chunk tiles): the chunk axis is minor and sequential,
each step streams a (BLOCK_C, p, n) tile through VMEM, and the running
(p, n) state lives in VMEM scratch across tiles — so VMEM use is set by
``BLOCK_C``, not by the sequence length, and the scan never round-trips
chunk states through HBM the way a lax.scan of small matmuls does.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_scan_kernel(states_ref, decay_ref, s0_ref, prev_ref, final_ref,
                     state_scr, *, block_c, ntiles):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0]

    def body(i, carry):
        prev_ref[0, i] = carry
        return carry * decay_ref[0, i, 0] + states_ref[0, i]

    state_scr[...] = jax.lax.fori_loop(0, block_c, body, state_scr[...])

    @pl.when(it == ntiles - 1)
    def _emit():
        final_ref[0, 0] = state_scr[...]


#: chunks per grid step: a (16, 64, 128) f32 tile is 512 KiB, so the
#: double-buffered in/out tiles stay far inside v5e's scoped VMEM
BLOCK_C = 16


def ssd_state_scan(states, decay, s0, *, interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """states: (b,c,h,p,n) fp32; decay: (b,c,h); s0: (b,h,p,n).

    Returns (prev_states (b,c,h,p,n), final (b,h,p,n)) — prev_states[c]
    is the state *entering* chunk c (matches ``ref.ssd_state_scan_ref``).
    A chunk count that ``BLOCK_C`` does not divide runs as one tile.
    """
    b, c, h, p, n = states.shape
    bc = BLOCK_C if c % BLOCK_C == 0 else c
    ntiles = c // bc
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # layout: move h next to b so one grid row owns a (c, p, n) stream
    st = states.transpose(0, 2, 1, 3, 4).reshape(b * h, c, p, n)
    dc = decay.transpose(0, 2, 1).reshape(b * h, c, 1)
    s0r = s0.reshape(b * h, 1, p, n)

    kernel = functools.partial(_ssd_scan_kernel, block_c=bc, ntiles=ntiles)
    prev, final = pl.pallas_call(
        kernel,
        grid=(b * h, ntiles),
        in_specs=[
            pl.BlockSpec((1, bc, p, n), lambda i, t: (i, t, 0, 0)),
            pl.BlockSpec((1, bc, 1), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, t: (i, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bc, p, n), lambda i, t: (i, t, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, t: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, c, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b * h, 1, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(st, dc, s0r)
    prev = prev.reshape(b, h, c, p, n).transpose(0, 2, 1, 3, 4)
    final = final.reshape(b, h, p, n)
    return prev, final

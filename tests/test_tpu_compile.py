"""Compile the Pallas kernels for a described TPU v5e chip (no chip
needed): the chip's compiler refuses block shapes off its tiling and
kernels that overflow VMEM, which interpret mode never sees.

Widths are the real ones: StarCoder2-7B attention (36 query heads, 4 KV
heads, head_dim 128) at batch 4 and 2048 tokens, and the mamba2-370m
SSD state scan (32 heads, p=64, n=128) at 128 chunks.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, so describing it
while pytest-xdist workers import this file would break collection.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ssd

B, H, HKV, HD, T = 4, 36, 4, 128, 2048
SSD_HEADS, SSD_P, SSD_N, SSD_C = 32, 64, 128, 128


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    bf, i32 = jnp.bfloat16, jnp.int32
    txt = _compiled_text(
        lambda q, k, v, qp, kp: fa.flash_attention(
            q, k, v, qp, kp, scale=HD ** -0.5, interpret=False),
        one_chip, ((B, H, T, HD), bf), ((B, HKV, T, HD), bf),
        ((B, HKV, T, HD), bf), ((B, T), i32), ((B, T), i32))
    assert "tpu_custom_call" in txt


def test_decode_attention_compiles_for_v5e(one_chip):
    bf, i32 = jnp.bfloat16, jnp.int32
    txt = _compiled_text(
        lambda q, k, v, kp, cur: dec.decode_attention(
            q, k, v, kp, cur, scale=HD ** -0.5, interpret=False),
        one_chip, ((B, H, HD), bf), ((B, HKV, T, HD), bf),
        ((B, HKV, T, HD), bf), ((B, T), i32), ((B,), i32))
    assert "tpu_custom_call" in txt


def test_ssd_state_scan_compiles_for_v5e(one_chip):
    f32 = jnp.float32
    txt = _compiled_text(
        lambda st, d, s0: ssd.ssd_state_scan(st, d, s0, interpret=False),
        one_chip, ((1, SSD_C, SSD_HEADS, SSD_P, SSD_N), f32),
        ((1, SSD_C, SSD_HEADS), f32), ((1, SSD_HEADS, SSD_P, SSD_N), f32))
    assert "tpu_custom_call" in txt

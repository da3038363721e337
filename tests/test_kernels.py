"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def mk(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,T,hd,bq,bk", [
    (1, 2, 2, 128, 128, 32, 64, 64),
    (2, 4, 2, 256, 256, 64, 128, 128),
    (1, 8, 1, 64, 192, 16, 64, 64),     # MQA, S != T
])
@pytest.mark.parametrize("window", [0, 48])
def test_flash_attention_sweep(dtype, B, H, Hkv, S, T, hd, bq, bk, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = mk(ks[0], (B, H, S, hd), dtype)
    k = mk(ks[1], (B, Hkv, T, hd), dtype)
    v = mk(ks[2], (B, Hkv, T, hd), dtype)
    off = T - S
    qpos = jnp.broadcast_to(jnp.arange(S) + off, (B, S))
    kpos = jnp.broadcast_to(jnp.arange(T), (B, T))
    scale = hd ** -0.5
    out = ops.flash_attention(q, k, v, qpos, kpos, scale=scale,
                              window=window, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, qpos, kpos, scale=scale,
                                   window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,T,hd,bk", [
    (2, 4, 2, 256, 64, 64),
    (1, 8, 8, 128, 32, 128),
    (3, 6, 2, 512, 16, 256),
])
@pytest.mark.parametrize("window", [0, 100])
def test_decode_attention_sweep(dtype, B, H, Hkv, T, hd, bk, window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = mk(ks[0], (B, H, hd), dtype)
    k = mk(ks[1], (B, Hkv, T, hd), dtype)
    v = mk(ks[2], (B, Hkv, T, hd), dtype)
    cur = jnp.asarray([T - 1, T // 2, T // 3][:B], jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(T), (B, T))
    kpos = jnp.where(kpos <= cur[:, None], kpos, -1)
    scale = hd ** -0.5
    out = ops.decode_attention(q, k, v, kpos, cur, scale=scale,
                               window=window, block_k=bk)
    want = ref.decode_attention_ref(q, k, v, kpos, cur, scale=scale,
                                    window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("b,c,h,p,n", [
    (1, 4, 2, 8, 16), (2, 8, 3, 16, 32), (1, 16, 1, 32, 8),
    (2, 48, 2, 8, 16),                  # three chunk tiles of 16
])
def test_ssd_scan_sweep(b, c, h, p, n):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    st = jax.random.normal(ks[0], (b, c, h, p, n), jnp.float32)
    dec = jax.random.uniform(ks[1], (b, c, h), jnp.float32)
    s0 = jax.random.normal(ks[2], (b, h, p, n), jnp.float32)
    prev, fin = ops.ssd_state_scan(st, dec, s0)
    pr, fr = ref.ssd_state_scan_ref(st, dec, s0)
    np.testing.assert_allclose(prev, pr, atol=1e-6)
    np.testing.assert_allclose(fin, fr, atol=1e-6)


def test_ssd_kernel_used_by_model():
    """ssm_forward(use_kernel=True) path agrees with the lax.scan path."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    b, l, h, p, n, chunk = 2, 64, 4, 16, 32, 16
    x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, l, 1, n), jnp.float32)
    Cm = jax.random.normal(ks[0], (b, l, 1, n), jnp.float32)
    y1, f1 = ssd_chunked(x, dt, A, Bm, Cm, chunk, use_kernel=False)
    y2, f2 = ssd_chunked(x, dt, A, Bm, Cm, chunk, use_kernel=True)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(f1, f2, atol=1e-4, rtol=1e-4)

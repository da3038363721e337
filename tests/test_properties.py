"""Hypothesis property tests on model-math invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.attention import _attend, blockwise_attention
from repro.models.layers import apply_rope
from repro.models.ssm import ssd_chunked, ssd_step


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(2, 24),
       st.integers(0, 1), st.integers(1, 3))
def test_blockwise_equals_dense_attention(B, Hkv, S, win_flag, g):
    H = Hkv * g
    hd = 8
    ks = jax.random.split(jax.random.PRNGKey(S * 7 + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    window = 4 if win_flag else 0
    out = blockwise_attention(q, k, v, pos, pos, window=window, scale=0.3,
                              block_q=5)
    mask = pos[:, :, None] >= pos[:, None, :]
    if window:
        mask &= (pos[:, :, None] - pos[:, None, :]) < window
    want = _attend(q, k, v, mask, 0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2), st.integers(4, 40), st.integers(1, 3),
       st.integers(2, 16))
def test_ssd_chunked_equals_stepwise(b, l, h, chunk):
    p, n = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(l * 31 + chunk), 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, l, 1, n))
    Cm = jax.random.normal(ks[4], (b, l, 1, n))
    y_chunk, final_chunk = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    # stepwise reference
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        state, y = ssd_step(state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    y_ref = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(final_chunk), np.asarray(state),
                               atol=2e-4, rtol=2e-3)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(1, 8), st.integers(2, 4))
def test_rope_preserves_norm_and_relativity(B, S, H):
    hd = 16
    ks = jax.random.split(jax.random.PRNGKey(S), 2)
    x = jax.random.normal(ks[0], (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    y = apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)
    # relativity: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(ks[1], (1, 1, 1, hd))
    k = jax.random.normal(ks[0], (1, 1, 1, hd))
    def score(i, j):
        qi = apply_rope(q, jnp.full((1, 1), i), 10_000.0)
        kj = apply_rope(k, jnp.full((1, 1), j), 10_000.0)
        return float(jnp.sum(qi * kj))
    assert abs(score(3, 1) - score(7, 5)) < 1e-4


def test_moe_gather_equals_dispatch_high_capacity():
    import dataclasses
    from repro.configs import get_arch, reduce_for_smoke
    from repro.dist.sharding import unbox
    from repro.models.moe import apply_moe, init_moe
    cfg = dataclasses.replace(
        reduce_for_smoke(get_arch("llama4-scout-17b-a16e")),
        dtype="float32", capacity_factor=8.0)
    params = unbox(init_moe(cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model),
                          jnp.float32) * 0.1
    y1, _ = apply_moe(params, x, cfg, decode=False)
    y2, _ = apply_moe(params, x, cfg, decode=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)


def test_moe_capacity_drops_tokens():
    """At tiny capacity the dispatch path must drop (not crash)."""
    import dataclasses
    from repro.configs import get_arch, reduce_for_smoke
    from repro.dist.sharding import unbox
    from repro.models.moe import apply_moe, init_moe
    cfg = dataclasses.replace(
        reduce_for_smoke(get_arch("llama4-scout-17b-a16e")),
        dtype="float32", capacity_factor=0.1)
    params = unbox(init_moe(cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = apply_moe(params, x, cfg)
    assert y.shape == x.shape
    assert not bool(jnp.isnan(y).any())

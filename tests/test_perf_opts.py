"""Paths that compute one result two ways must agree: the decode cache's
two writers, the MoE's decode gather and its capacity dispatch; and the
sharding rules' head_dim fallback."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, get_shape, reduce_for_smoke
from repro.dist.sharding import rules_for, unbox
from repro.models import model
from repro.models.attention import write_decode_rows
from repro.models.moe import apply_moe, init_moe


def _fp32(name):
    return dataclasses.replace(reduce_for_smoke(get_arch(name)),
                               dtype="float32")


def test_where_cache_update_equivalent():
    """write_decode_rows' scatter and its per-slot writer put two rows at
    slots 5 and 11 of a dense model's cache and leave the rest alone."""
    cfg = _fp32("stablelm-12b")
    cache = model.init_decode_cache(cfg, 3, 16)["dense"]
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    cache = {name: (jax.random.randint(next(keys), c.shape, -1, 16, c.dtype)
                    if name == "pos" else
                    jax.random.normal(next(keys), c.shape, c.dtype))
             for name, c in cache.items()}
    L, _, _, hkv, hd = cache["k"].shape
    rows = {name: jax.random.normal(next(keys), (L, 2, hkv, hd))
            for name in ("k", "v")}
    cur = jnp.asarray([5, 11], jnp.int32)
    scatter = write_decode_rows(cache, rows, cur)
    per_slot = write_decode_rows(cache, rows, cur, per_slot=True)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), scatter, per_slot)
    for b, s in enumerate((5, 11)):
        for name in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(scatter[name][:, b, s]),
                                          np.asarray(rows[name][:, b]))
        assert (np.asarray(scatter["pos"][:, b, s]) == s).all()
    written = np.zeros(cache["pos"].shape, bool)
    written[:, 0, 5] = written[:, 1, 11] = True
    for name, c in cache.items():
        np.testing.assert_array_equal(np.asarray(scatter[name])[~written],
                                      np.asarray(c)[~written])


def test_moe_decode_dispatch_equivalent():
    """The decode's per-token expert gather equals capacity dispatch when
    no token is dropped."""
    cfg = dataclasses.replace(
        reduce_for_smoke(get_arch("llama4-scout-17b-a16e")),
        dtype="float32", capacity_factor=8.0)  # no drops
    params = unbox(init_moe(cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32) * 0.1
    gather, _ = apply_moe(params, x, cfg, decode=True)
    dispatch, _ = apply_moe(params, x, cfg, decode=False)
    np.testing.assert_allclose(np.asarray(gather), np.asarray(dispatch),
                               atol=1e-4, rtol=1e-4)


def test_rules_for_opts():
    """KV heads that do not divide the model axis shard head_dim."""
    cfg = get_arch("qwen2-72b")
    rules = rules_for(cfg, get_shape("decode_32k"), 16)
    assert rules["head_dim"] == "model"
    assert rules["kv_heads"] is None

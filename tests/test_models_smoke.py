"""Per-architecture smoke tests: reduced variant, one forward + one train
step on CPU; output shapes + no NaNs; prefill+decode == full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduce_for_smoke
from repro.dist.sharding import unbox
from repro.models import model
from repro.serving.engine import _write_slot
from repro.train.loop import make_train_step
from repro.train.optimizer import AdamW

ALL_ARCHS = sorted(ARCHS)


def smoke_cfg(name, **kw):
    cfg = reduce_for_smoke(get_arch(name))
    return dataclasses.replace(cfg, **kw) if kw else cfg


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name, **kw):
        key = (name, tuple(sorted(kw.items())))
        if key not in cache:
            cfg = smoke_cfg(name, **kw)
            params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
            cache[key] = (cfg, params)
        return cache[key]

    return get


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_forward_shapes_no_nan(built, name):
    cfg, params = built(name)
    B, S = 2, 16
    batch = model.make_inputs(cfg, B, S, key=jax.random.PRNGKey(1))
    logits, _, aux = model.forward(cfg, params, batch)
    S_out = S if cfg.family != "vlm" else S
    assert logits.shape == (B, S_out, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())
    loss = model.lm_loss(cfg, logits, batch)
    assert float(loss) > 0 and not bool(jnp.isnan(loss))


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_one_train_step(built, name):
    cfg, params = built(name)
    opt = AdamW(lr=1e-3)
    step = make_train_step(cfg, opt, donate=False)
    batch = {k: jnp.asarray(v) for k, v in model.make_inputs(
        cfg, 2, 16, key=jax.random.PRNGKey(2)).items()}
    p2, _, metrics = step(params, opt.init(params), batch)
    assert float(metrics["loss"]) > 0
    assert not bool(jnp.isnan(metrics["loss"]))
    # params actually moved
    diff = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                                - b.astype(jnp.float32)).sum()),
                     params, p2))
    assert diff > 0


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_decode_matches_full_forward(name):
    cfg = smoke_cfg(name, dtype="float32",
                    capacity_factor=8.0)
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    S = 12
    batch = model.make_inputs(cfg, 2, S, key=jax.random.PRNGKey(7))
    logits_full, _, _ = model.forward(cfg, params, batch)
    ntok = batch["tokens"].shape[1]
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :ntok - 1]
    _, pcache, _ = model.forward(cfg, params, pre, return_cache=True)
    off = batch["patches"].shape[1] if cfg.family == "vlm" else 0
    dcache = model.init_decode_cache(cfg, 2, ntok + off + 4)
    dcache = model.merge_prefill_cache(dcache, pcache)
    cur = jnp.full((2,), ntok - 1 + off, jnp.int32)
    lg, _ = model.decode_step(cfg, params, batch["tokens"][:, ntok - 1:ntok],
                              dcache, cur)
    err = float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, -1])))
    assert err < 1e-3, err


def test_sliding_window_changes_logits():
    cfg = smoke_cfg("gemma-7b", dtype="float32")
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    batch = model.make_inputs(cfg, 1, 32, key=jax.random.PRNGKey(3))
    full, _, _ = model.forward(cfg, params, batch)
    win, _, _ = model.forward(cfg, params, batch, window=4)
    # early positions identical (window covers history), late differ
    assert float(jnp.max(jnp.abs(full[:, 2] - win[:, 2]))) < 1e-4
    assert float(jnp.max(jnp.abs(full[:, -1] - win[:, -1]))) > 1e-6


def test_windowed_decode_matches_windowed_forward():
    cfg = smoke_cfg("qwen2-72b", dtype="float32")
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    S, W = 12, 4
    batch = model.make_inputs(cfg, 2, S, key=jax.random.PRNGKey(5))
    full, _, _ = model.forward(cfg, params, batch, window=W)
    pre = {"tokens": batch["tokens"][:, :S - 1]}
    _, pcache, _ = model.forward(cfg, params, pre, return_cache=True,
                                 window=W)
    # ring cache of size W
    dcache = model.init_decode_cache(cfg, 2, S + 4, window=W)
    # write last W-1 positions of prefill cache into the ring
    import jax.numpy as jnp2

    def ring_write(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        Wd = dst.shape[2]
        out = dst
        Spre = src.shape[2]
        for p in range(max(0, Spre - Wd), Spre):
            out = out.at[:, :, p % Wd].set(src[:, :, p].astype(dst.dtype))
        return out

    dcache = jax.tree.map(ring_write, dcache, pcache)
    cur = jnp.full((2,), S - 1, jnp.int32)
    lg, _ = model.decode_step(cfg, params, batch["tokens"][:, S - 1:],
                              dcache, cur, window=W)
    err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, -1])))
    assert err < 1e-3, err


# --------------------------------------------------------------------------
# Decode cache: a reused slot, the rows a step writes, and the program's
# structure
# --------------------------------------------------------------------------

def _prefill_cache(cfg, params, tokens):
    _, cache, _ = model.forward(
        cfg, params, {"tokens": jnp.asarray(tokens, jnp.int32)[None]},
        return_cache=True)
    return cache


@pytest.mark.parametrize("name,window", [
    ("starcoder2-7b", 0),     # dense, full cache
    ("qwen2-72b", 8),         # sliding window: a ring of 8 slots
    ("zamba2-7b", 0),         # hybrid: SSM layers and a shared attention
])
def test_reused_slot_decodes_like_a_fresh_one(name, window):
    """A slot that served a longer request keeps its rows and positions at
    and beyond the new request's length (one of them at exactly the
    position decoded next).  Prefilling a shorter prompt into it, as the
    engine does, and decoding must give the logits of a fresh cache and
    of the full forward."""
    cfg = smoke_cfg(name, dtype="float32", sliding_window=window)
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(3)
    long_p = rng.integers(1, cfg.vocab_size, 8)
    short_p = rng.integers(1, cfg.vocab_size, 5)
    other_p = rng.integers(1, cfg.vocab_size, 3)
    nxt = np.asarray([[11], [17]], np.int32)
    max_seq = 16

    # slot 0 serves the long request: prefill, then decode past its end
    # (past the ring's end when windowed); slot 1 idles at position 0
    used = model.init_decode_cache(cfg, 2, max_seq)
    used = _write_slot(used, _prefill_cache(cfg, params, long_p),
                       jnp.int32(0))
    for p in range(len(long_p), len(long_p) + 3):
        _, used = model.decode_step(cfg, params, jnp.asarray(nxt),
                                    used, jnp.asarray([p, 0], jnp.int32))
    fresh = model.init_decode_cache(cfg, 2, max_seq)
    for slot, prompt in ((0, short_p), (1, other_p)):
        pc = _prefill_cache(cfg, params, prompt)
        used = _write_slot(used, pc, jnp.int32(slot))
        fresh = _write_slot(fresh, pc, jnp.int32(slot))
    attn = used["attn" if cfg.family == "hybrid" else "dense"]
    cur = len(short_p)
    assert int(attn["pos"][0, 0, cur % attn["pos"].shape[-1]]) == cur

    pos = jnp.asarray([len(short_p), len(other_p)], jnp.int32)
    lg_used, _ = model.decode_step(cfg, params, jnp.asarray(nxt), used, pos)
    lg_fresh, _ = model.decode_step(cfg, params, jnp.asarray(nxt), fresh,
                                    pos)
    np.testing.assert_allclose(np.asarray(lg_used), np.asarray(lg_fresh),
                               atol=1e-5, rtol=1e-5)
    for row, prompt in ((0, short_p), (1, other_p)):
        full, _, _ = model.forward(cfg, params, {"tokens": jnp.asarray(
            np.append(prompt, nxt[row]), jnp.int32)[None]})
        err = float(jnp.max(jnp.abs(lg_used[row, 0] - full[0, -1])))
        assert err < 1e-3, (row, err)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2.0 ** -6)])
def test_decode_writes_only_the_new_rows(dtype, tol):
    """After a decode step the cache is the old cache with each row's new
    K/V at [layer, b, cur_pos % W] and pos there set to cur_pos; every other
    entry is unchanged, bit for bit.  The written rows are the keys and
    values the full forward computes at that position (in bfloat16 to
    within two units in the last place: the forward rounds its hidden
    states in another order)."""
    cfg = smoke_cfg("starcoder2-7b", dtype=dtype)
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 9), 1,
                              cfg.vocab_size, jnp.int32)
    lens = [6, 8]
    cache = model.init_decode_cache(cfg, 2, 16)
    for b, n in enumerate(lens):
        cache = _write_slot(cache, _prefill_cache(cfg, params, toks[b, :n]),
                            jnp.int32(b))
    cur = jnp.asarray(lens, jnp.int32)
    step_tok = jnp.stack([toks[b, n] for b, n in enumerate(lens)])[:, None]
    _, new = model.decode_step(cfg, params, step_tok, cache, cur)

    old, new = cache["dense"], new["dense"]
    L, B, W = old["pos"].shape
    written = np.zeros((L, B, W), bool)
    written[:, np.arange(B), np.asarray(cur) % W] = True
    for name in ("k", "v", "pos"):
        a, b = np.asarray(old[name]), np.asarray(new[name])
        np.testing.assert_array_equal(a[~written], b[~written])
    np.testing.assert_array_equal(
        np.asarray(new["pos"])[written].reshape(L, B),
        np.broadcast_to(np.asarray(cur), (L, B)))
    for b, n in enumerate(lens):
        ref = _prefill_cache(cfg, params, toks[b, :n + 1])["dense"]
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(new[name][:, b, n], np.float32),
                np.asarray(ref[name][:, 0, n], np.float32),
                atol=tol, rtol=tol)


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def test_decode_program_reads_the_cache_in_place():
    """The decode's layer scan emits no array with the cache's sequence
    axis (only the new rows), and no cache-shaped array is converted to
    float32: QK and AV read the stored bfloat16."""
    cfg = smoke_cfg("starcoder2-7b")
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    B, W = 2, 40                 # W matches no other dimension
    cache = model.init_decode_cache(cfg, B, W)
    kv = cache["dense"]["k"].shape                    # (L, B, W, Hkv, hd)
    assert W not in kv[:2] + kv[3:]
    jaxpr = jax.make_jaxpr(
        lambda t, c, p: model.decode_step(cfg, params, t, c, p))(
        jnp.zeros((B, 1), jnp.int32), cache, jnp.zeros((B,), jnp.int32))
    eqns = list(_all_eqns(jaxpr.jaxpr))
    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert scans
    for e in scans:
        for v in e.outvars:
            assert W not in v.aval.shape, v.aval
    cache_shapes = {kv, kv[1:]}
    for e in eqns:
        if e.primitive.name == "convert_element_type" \
                and e.params["new_dtype"] == jnp.float32:
            assert e.invars[0].aval.shape not in cache_shapes, e

"""Zamba2's hybrid block at a small size on the CPU: the grouped Mamba2
mixer, the shared blocks and their per-use adapters, the program against
the plain float32 reference (``bench/references/zamba2_lm.py``), and
mamba2-370m left as it was."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduce_for_smoke
from repro.configs.base import ModelConfig
from repro.dist.sharding import unbox
from repro.models import model, ssm, transformer
from repro.serving.engine import _write_slot

REF = Path(__file__).resolve().parents[1] / "bench" / "references" / \
    "zamba2_lm.py"

#: published keys of a small Zamba2 and the engine's config of the same
#: model: 7 Mamba2 layers, shared blocks A, B, A before layers 2, 3 and 5
PUBLISHED = {
    "hidden_size": 64, "mamba_expand": 2, "n_mamba_heads": 8,
    "mamba_headdim": 16, "mamba_d_state": 16, "mamba_ngroups": 2,
    "mamba_d_conv": 4, "attention_hidden_size": 128,
    "attention_head_dim": 32, "num_attention_heads": 4,
    "ffn_hidden_size": 128, "adapter_rank": 8, "vocab_size": 256,
    "num_mem_blocks": 2, "num_hidden_layers": 7,
    "hybrid_layer_ids": [2, 3, 5], "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 1e-4, "torch_dtype": "float32"}
CFG = ModelConfig(
    name="zamba2-test", family="hybrid", num_layers=7, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=32, attn_scale=16 ** -0.5,
    d_ff=128, vocab_size=256, act="gelu", norm_eps=1e-5,
    tie_embeddings=True, ssm_state=16, ssm_headdim=16, ssm_chunk=8,
    ssm_ngroups=2, hybrid_layer_ids=(2, 3, 5), num_mem_blocks=2,
    adapter_rank=8, dtype="float32")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("zamba2_lm_ref", REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def weights(ref):
    return ref.make_weights(PUBLISHED, 11)


def _tokens(n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 256)


def _logits(params, tokens):
    with jax.default_matmul_precision("highest"):
        lg, _, _ = model.forward(CFG, params, {"tokens": tokens[None]})
    return lg[0]


# ------------------------------------------------------------ the mixer
@pytest.mark.parametrize("groups", [1, 2])
def test_grouped_ssd_chunked_equals_the_token_recurrence(groups):
    b, l, h, p, n, chunk = 2, 37, 8, 4, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(groups), 6)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, l, groups, n))
    Cm = jax.random.normal(ks[4], (b, l, groups, n))
    s0 = jax.random.normal(ks[5], (b, h, p, n))
    with jax.default_matmul_precision("highest"):
        y, final = ssm.ssd_chunked(x, dt, A, Bm, Cm, chunk, initial_state=s0)
    # head i reads group i // (h / groups), written out by hand
    grp = np.arange(h) // (h // groups)
    state, ys = np.asarray(s0, np.float64), []
    for t in range(l):
        Bt = np.asarray(Bm[:, t])[:, grp]                    # (b, h, n)
        Ct = np.asarray(Cm[:, t])[:, grp]
        dtt = np.asarray(dt[:, t])
        state = (state * np.exp(dtt * np.asarray(A))[..., None, None]
                 + (dtt[..., None] * np.asarray(x[:, t]))[..., None]
                 * Bt[:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", state, Ct))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), state, rtol=2e-4,
                               atol=2e-4)
    # the decode step is the same recurrence
    st = s0
    for t in range(l):
        st, yt = ssm.ssd_step(st, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
    np.testing.assert_allclose(np.asarray(st), state, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(yt), ys[-1], rtol=2e-4, atol=2e-4)


def test_mamba2_conv_state_and_skip_continue_from_prefill_into_decode():
    """A prefill of the first S tokens hands its conv inputs and SSM state
    to the decode, which then gives, token by token, what the mixer gives
    over the whole sequence (conv window, state and D skip included)."""
    cfg = dataclasses.replace(CFG, ssm_chunk=4)
    params = unbox(ssm.init_ssm(cfg, jax.random.PRNGKey(2)))
    params["D"] = jnp.linspace(0.5, 2.0, cfg.ssm_nheads)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 13, cfg.d_model))
    S = 6
    with jax.default_matmul_precision("highest"):
        full, _ = ssm.ssm_forward(params, x, cfg)
        _, cache = ssm.ssm_forward(params, x[:, :S], cfg, return_cache=True)
        outs = []
        for t in range(S, x.shape[1]):
            y, cache = ssm.ssm_decode(params, x[:, t:t + 1], cfg, cache)
            outs.append(y[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full[:, S:]), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- the shared blocks
def test_the_hybrid_groups_follow_the_hybrid_layer_ids():
    assert transformer.hybrid_groups(CFG) == [
        (0, 2, None), (2, 3, 0), (3, 5, 1), (5, 7, 2)]
    smoke = reduce_for_smoke(get_arch("zamba2-7b"))
    assert transformer.hybrid_groups(smoke) == [(0, 1, 0), (1, 2, 1)]
    cut = dataclasses.replace(get_arch("zamba2-7b"), num_layers=41,
                              hybrid_layer_ids=(6, 11, 17, 23, 29, 35))
    assert [hi - lo for lo, hi, _ in transformer.hybrid_groups(cut)] == \
        [6, 5, 6, 6, 6, 6, 6]


def test_both_memory_blocks_are_used_in_turn(weights):
    """Uses 0, 1, 2 take blocks A, B, A (the reference's order, which the
    tests below match): swapping the two blocks changes the output, and so
    does scaling block B, which only use 1 reads."""
    toks = _tokens(12)
    base = _logits(weights, toks)
    swapped = dict(weights, shared=weights["shared"][::-1])
    assert float(jnp.max(jnp.abs(_logits(swapped, toks) - base))) > 1e-3
    noisy_b = jax.tree.map(lambda a: a * 1.5, weights["shared"][1])
    changed = dict(weights, shared=[weights["shared"][0], noisy_b])
    assert float(jnp.max(jnp.abs(_logits(changed, toks) - base))) > 1e-3


@pytest.mark.parametrize("use", [0, 1, 2])
def test_each_use_has_its_own_adapter_and_linear(weights, use):
    toks = _tokens(12)
    base = _logits(weights, toks)
    for name in ("lora_b", "linear"):
        uses = list(weights["uses"])
        uses[use] = dict(uses[use], **{name: uses[use][name] * 2.0})
        out = _logits(dict(weights, uses=uses), toks)
        assert float(jnp.max(jnp.abs(out - base))) > 1e-3, (use, name)


# --------------------------------------------------- against the reference
def test_forward_matches_the_float32_reference(ref, weights):
    toks = _tokens(21)
    best, arg, picked = ref.token_stats_fn(PUBLISHED)(weights, toks,
                                                      toks[None])
    lg = _logits(weights, toks)
    np.testing.assert_allclose(np.asarray(lg.max(-1)), np.asarray(best),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(lg, toks[:, None], 1)[:, 0]),
        np.asarray(picked[0]), atol=1e-4, rtol=1e-4)
    assert (np.asarray(jnp.argmax(lg, -1)) == np.asarray(arg)).all()


def test_prefill_then_decode_matches_the_reference(ref, weights):
    """Two slots, prefilled through the engine's slot write, decode token
    by token through the cache; every decoded position's logits are the
    reference's full forward's."""
    toks = np.asarray(_tokens(20, seed=4))
    lens = (9, 13)
    cache = model.init_decode_cache(CFG, 2, 24)
    with jax.default_matmul_precision("highest"):
        for b, S in enumerate(lens):
            _, pc, _ = model.forward(CFG, weights,
                                     {"tokens": jnp.asarray(toks[None, :S])},
                                     return_cache=True)
            cache = _write_slot(cache, pc, jnp.int32(b))
        steps = []
        for k in range(6):
            pos = jnp.asarray([S + k for S in lens], jnp.int32)
            lg, cache = model.decode_step(CFG, weights,
                                          jnp.asarray(toks[np.asarray(pos)])
                                          [:, None], cache, pos)
            steps.append(np.asarray(lg[:, 0]))
    stats = ref.token_stats_fn(PUBLISHED)
    for b, S in enumerate(lens):
        best, _, picked = stats(weights, jnp.asarray(toks),
                                jnp.asarray(toks)[None])
        got = np.stack([s[b] for s in steps])                  # (6, V)
        want_best = np.asarray(best)[S:S + 6]
        np.testing.assert_allclose(got.max(-1), want_best, atol=1e-4,
                                   rtol=1e-4)
        # picked[0][p] is the logit at position p of the token toks[p]
        fed = toks[S:S + 6]
        np.testing.assert_allclose(got[np.arange(6), fed],
                                   np.asarray(picked[0])[S:S + 6],
                                   atol=1e-4, rtol=1e-4)


def test_the_decode_carries_the_state_and_emits_no_copy_of_it(weights):
    """Each group's scan takes the stacked state in its carry and emits
    nothing per layer: no scan output has a state's shape."""
    cache = model.init_decode_cache(CFG, 2, 24)
    jaxpr = jax.make_jaxpr(
        lambda c: model.decode_step(CFG, weights, jnp.zeros((2, 1),
                                                            jnp.int32),
                                    c, jnp.zeros((2,), jnp.int32)))(cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == len(cache["ssm"])
    for e in scans:
        n_carry = e.params["num_carry"]
        assert not e.outvars[n_carry:], e.outvars[n_carry:]


# ---------------------------------------------------------- mamba2-370m
def test_mamba2_370m_is_unchanged():
    """One group (G = 1), its tree and cache shapes as before, and its
    logits those the implementation with shared B/C gave (recorded at this
    size before the groups came in)."""
    cfg = dataclasses.replace(reduce_for_smoke(get_arch("mamba2-370m")),
                              dtype="float32")
    assert cfg.ssm_ngroups == 1
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    mixer = params["layers"]["mixer"]
    assert mixer["in_proj"].shape == (2, 256, 1120)
    assert mixer["conv_w"].shape == (2, 4, 576)
    dc = model.init_decode_cache(cfg, 2, 64)
    assert jax.tree.map(lambda a: a.shape, dc) == {
        "ssm": {"conv": (2, 2, 3, 576), "ssm": (2, 2, 32, 16, 32)}}
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 40), 0,
                              cfg.vocab_size)
    lg, _, _ = model.forward(cfg, params, {"tokens": toks})
    np.testing.assert_allclose(
        np.asarray(lg[:, -1, :6]),
        [[-0.02074, -0.09423, -0.31154, -0.01273, 1.79416, -0.23787],
         [-0.14301, 0.36057, 0.02382, 0.30773, -0.64705, -0.03234]],
        atol=2e-5)
    d, _ = model.decode_step(cfg, params, toks[:, :1], dc,
                             jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(
        np.asarray(d[:, 0, :4]),
        [[-0.20143, -0.1392, 0.23341, 2.23141],
         [-0.25867, -0.49451, 0.98548, -0.26356]], atol=2e-5)


def test_the_published_config_counts_7_357e9_parameters():
    cfg = get_arch("zamba2-7b")
    assert cfg.hybrid_layer_ids == (6, 11, 17, 23, 29, 35, 41, 47, 53, 59,
                                    65, 71, 77)
    assert cfg.ssm_conv_dim == 7168 + 2 * 2 * 64
    assert abs(cfg.param_count() - 7.357e9) < 0.001e9


def test_the_mixer_and_shared_block_scopes_reach_the_compiled_program(
        weights):
    """``mamba2.in_proj``, ``mamba2.ssd``, ``mamba2.out`` and
    ``zamba2.shared`` name the ops of the prefill and of the decode in the
    compiled program's metadata, where a profile reads them."""
    cache = model.init_decode_cache(CFG, 2, 24)
    decode = jax.jit(lambda c: model.decode_step(
        CFG, weights, jnp.zeros((2, 1), jnp.int32), c,
        jnp.zeros((2,), jnp.int32))).lower(cache).compile().as_text()
    prefill = jax.jit(lambda t: model.forward(
        CFG, weights, {"tokens": t}, return_cache=True)).lower(
        jnp.zeros((1, 16), jnp.int32)).compile().as_text()
    for scope in ("mamba2.in_proj", "mamba2.ssd", "mamba2.out",
                  "zamba2.shared"):
        assert scope in decode and scope in prefill, scope

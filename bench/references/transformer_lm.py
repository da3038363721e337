"""Plain float32 reference of a dense decoder-only LM (the StarCoder2 block).

Reads the published config keys (``hidden_size``, ``num_key_value_heads``,
...) of a configuration file under ``bench/configs`` and imports nothing
of the program under test.

- ``make_weights``: the benchmark's random weights, drawn on the device
  from the seed in one jitted call, in the dtype they are served in and
  in the tree layout the serving engine takes.
- ``token_stats``: a no-cache forward over whole sequences in float32 at
  ``Precision.HIGHEST``, layer by layer and in blocks of queries and
  vocabulary, so that it fits beside the served weights. It returns the
  per-position maximum logit, its argmax and the logits of chosen tokens.
- ``token_stats(..., fp8=True)`` is the control: the same forward with
  both operands of every weight matmul rounded to float8 e4m3 (per-row
  activation scales, per-output-channel weight scales), the step below the
  configuration's bfloat16.

Block: pre-LayerNorm, GQA attention with q/k/v biases and half-split RoPE,
a GELU (tanh) MLP, a final LayerNorm and an untied head. The attention
output and MLP biases of ``use_bias`` are zero in these weights (see the
configuration's ``assumed``), so the reference leaves them out.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                 # largest finite float8_e4m3fn
Q_BLOCK = 512                   # query rows per attention block
V_BLOCK = 8192                  # vocabulary columns per head block
F_BLOCKS = 4                    # MLP hidden-width blocks


def dims(c: Dict) -> Dict[str, int]:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "D": D, "H": H,
            "KV": c["num_key_value_heads"], "hd": c.get("head_dim", D // H),
            "F": c["intermediate_size"], "V": c["vocab_size"]}


def prng_key(seed: int):
    """A JAX key for any non-negative seed that fits 64 bits."""
    lo, hi = np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.key(lo), hi)


def make_weights(c: Dict, seed: int):
    """Random weights for configuration ``c`` in the serving engine's tree
    layout (layer stacks under ``dense_layers``), made on the device."""
    d = dims(c)
    D, H, KV, hd, F, V = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["V"]
    dt = jnp.dtype(c["torch_dtype"])
    f32 = jnp.float32

    def normal(key, shape, scale, dtype, mean=0.0):
        return (mean + jax.random.normal(key, shape, f32) * scale).astype(dtype)

    def norm(k1, k2):
        return {"scale": normal(k1, (D,), 0.02, f32, mean=1.0),
                "bias": normal(k2, (D,), 0.02, f32)}

    def layer(key):
        ks = jax.random.split(key, 13)
        return {
            "norm1": norm(ks[0], ks[1]),
            "attn": {"wq": normal(ks[2], (D, H * hd), D ** -0.5, dt),
                     "wk": normal(ks[3], (D, KV * hd), D ** -0.5, dt),
                     "wv": normal(ks[4], (D, KV * hd), D ** -0.5, dt),
                     "wo": normal(ks[5], (H * hd, D), (H * hd) ** -0.5, dt),
                     "bq": normal(ks[6], (H * hd,), 0.02, dt),
                     "bk": normal(ks[7], (KV * hd,), 0.02, dt),
                     "bv": normal(ks[8], (KV * hd,), 0.02, dt)},
            "norm2": norm(ks[9], ks[10]),
            "mlp": {"wi": normal(ks[11], (D, F), D ** -0.5, dt),
                    "wo": normal(ks[12], (F, D), F ** -0.5, dt)},
        }

    @jax.jit
    def make(key):
        ke, kh, k1, k2, kl = jax.random.split(key, 5)
        # lax.map draws one layer at a time: only that layer's float32
        # draws are live beside the stacked result
        layers = jax.lax.map(layer, jax.random.split(kl, d["L"]))
        return {"embed": {"tok": normal(ke, (V, D), D ** -0.5, dt),
                          "head": normal(kh, (D, V), D ** -0.5, dt)},
                "final_norm": norm(k1, k2),
                "dense_layers": layers}

    return make(prng_key(seed))


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """a (..., K) float32 times a stored weight w (K, N)."""
    w = w.astype(jnp.float32)
    if fp8:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta):
    """x (T, heads, hd); rotates the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def token_stats_fn(c: Dict, fp8: bool = False):
    """A jitted ``f(weights, tokens (T,), chosen (K, T)) -> (max (T,),
    argmax (T,), picked (K, T))`` over the logits at every position."""
    return _token_stats_fn(json.dumps(c, sort_keys=True), fp8)


@functools.lru_cache(maxsize=8)
def _token_stats_fn(config_json: str, fp8: bool):
    c = json.loads(config_json)
    d = dims(c)
    D, H, KV, hd, F, V = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["V"]
    g = H // KV
    eps, theta = c["norm_epsilon"], c["rope_theta"]
    scale = 1.0 / math.sqrt(hd)

    def attention(q, k, v, pos):
        """q (T, H, hd), k/v (T, KV, hd): causal GQA, in query blocks."""
        T = q.shape[0]
        outs = []
        for s0 in range(0, T, Q_BLOCK):
            qb = q[s0:s0 + Q_BLOCK].reshape(-1, KV, g, hd)
            sc = jnp.einsum("skgd,tkd->kgst", qb, k,
                            precision=HIGHEST) * scale
            causal = pos[None, :] <= pos[s0:s0 + Q_BLOCK, None]
            sc = jnp.where(causal[None, None], sc, -jnp.inf)
            w = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("kgst,tkd->skgd", w, v, precision=HIGHEST)
            outs.append(o.reshape(-1, H * hd))
        return jnp.concatenate(outs, 0)

    def block(x, lp):
        pos = jnp.arange(x.shape[0])
        a = lp["attn"]
        h = _ln(x, lp["norm1"], eps)
        q = (_mm(h, a["wq"], fp8) + a["bq"].astype(jnp.float32))
        k = (_mm(h, a["wk"], fp8) + a["bk"].astype(jnp.float32))
        v = (_mm(h, a["wv"], fp8) + a["bv"].astype(jnp.float32))
        q = _rope(q.reshape(-1, H, hd), pos, theta)
        k = _rope(k.reshape(-1, KV, hd), pos, theta)
        v = v.reshape(-1, KV, hd)
        x = x + _mm(attention(q, k, v, pos), a["wo"], fp8)
        h = _ln(x, lp["norm2"], eps)
        m = lp["mlp"]
        fb = F // F_BLOCKS
        for f0 in range(0, F, fb):
            u = jax.nn.gelu(_mm(h, m["wi"][:, f0:f0 + fb], fp8),
                            approximate=True)
            x = x + _mm(u, m["wo"][f0:f0 + fb], fp8)
        return x, None

    @jax.jit
    def stats(w, tokens, chosen):
        x = jnp.take(w["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
        x, _ = jax.lax.scan(block, x, w["dense_layers"])
        h = _ln(x, w["final_norm"], eps)
        T = tokens.shape[0]
        best = jnp.full((T,), -jnp.inf, jnp.float32)
        arg = jnp.zeros((T,), jnp.int32)
        picked = jnp.zeros(chosen.shape, jnp.float32)
        for v0 in range(0, V, V_BLOCK):
            lg = _mm(h, w["embed"]["head"][:, v0:v0 + V_BLOCK], fp8)
            n = lg.shape[1]
            bmax = lg.max(axis=1)
            barg = jnp.argmax(lg, axis=1).astype(jnp.int32) + v0
            arg = jnp.where(bmax > best, barg, arg)
            best = jnp.maximum(best, bmax)
            inside = (chosen >= v0) & (chosen < v0 + n)
            idx = jnp.clip(chosen - v0, 0, n - 1)
            got = jnp.stack([jnp.take_along_axis(lg, i[:, None], axis=1)[:, 0]
                             for i in idx])
            picked = jnp.where(inside, got, picked)
        return best, arg, picked

    return stats

"""Plain float32 reference of Zamba2's hybrid stack (Mamba2 layers and two
shared attention+MLP blocks).

Reads the published config keys (``hidden_size``, ``hybrid_layer_ids``,
``mamba_ngroups``, ...) of a configuration file under ``bench/configs``
and imports nothing of the program under test.

- ``make_weights``: the benchmark's random weights, drawn on the device
  from the seed in one jitted call, in the dtype they are served in and
  in the tree layout the serving engine takes: ``layers`` holds one stack
  of Mamba2 layers per group (a group starts at layer 0 or at a hybrid
  layer and runs to the next), ``shared`` the ``num_mem_blocks`` shared
  blocks, ``uses`` each hybrid layer's MLP adapter and output linear.
- ``token_stats``: a no-cache forward over whole sequences in float32 at
  ``Precision.HIGHEST``. The Mamba2 mixer is the token-by-token recurrence
  (``lax.scan`` over positions), not the chunked SSD; attention runs in
  blocks of queries and the head in blocks of vocabulary, so that it fits
  beside the served weights. It returns the per-position maximum logit,
  its argmax and the logits of chosen tokens.
- ``token_stats(..., fp8=True)`` is the control: the same forward with
  both operands of every weight matmul rounded to float8 e4m3 (per-row
  activation scales, per-output-channel weight scales), the step below the
  configuration's bfloat16.

The layer equations, with x0 the embedding output and h the residual::

    for l in 0..L-1:
      if l is the j-th hybrid layer:         block b = j % num_mem_blocks
        u = RMSNorm_b,in([h, x0]);  o = Attn_b(u)       (RoPE, causal)
        m = RMSNorm_b,ff(o);  [g, up] = m W_gu,b + (m A_j) B_j
        t = ((gelu(g) * up) W_down,b) W_lin,j
        h = h + Mamba2_l(RMSNorm_l(h + t))
      else:
        h = h + Mamba2_l(RMSNorm_l(h))
    logits = RMSNorm_f(h) Emb^T

Mamba2: in_proj to [z, x|B|C, dt]; a causal depthwise conv of width
``mamba_d_conv`` with bias and SiLU over x|B|C; per head h (group
h // (H/G)) the state s <- exp(dt A) s + dt x B^T, y = s C + D x; then
y * silu(z), an RMSNorm over each group's channels, and out_proj.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                 # largest finite float8_e4m3fn
Q_BLOCK = 512                   # query rows per attention block
V_BLOCK = 8192                  # vocabulary columns per head block


def dims(c: Dict) -> Dict[str, int]:
    D = c["hidden_size"]
    di = c["mamba_expand"] * D
    G, N = c["mamba_ngroups"], c["mamba_d_state"]
    return {"L": c["num_hidden_layers"], "D": D, "di": di, "G": G, "N": N,
            "H": c["n_mamba_heads"], "P": c["mamba_headdim"],
            "K": c["mamba_d_conv"], "C": di + 2 * G * N,
            "A": c["attention_hidden_size"], "heads": c["num_attention_heads"],
            "hd": c["attention_head_dim"], "F": c["ffn_hidden_size"],
            "r": c["adapter_rank"], "V": c["vocab_size"],
            "M": c["num_mem_blocks"]}


def groups(c: Dict) -> List[Tuple[int, int, int]]:
    """(first, last + 1, use) of each run of Mamba2 layers that starts at
    layer 0 or at a hybrid layer; ``use`` is the hybrid layer's index in
    ``hybrid_layer_ids``, -1 for the layers before the first."""
    ids = list(c["hybrid_layer_ids"])
    starts = sorted({0, *ids})
    ends = starts[1:] + [c["num_hidden_layers"]]
    return [(a, b, ids.index(a) if a in ids else -1)
            for a, b in zip(starts, ends)]


def prng_key(seed: int):
    """A JAX key for any non-negative seed that fits 64 bits."""
    lo, hi = np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.key(lo), hi)


def make_weights(c: Dict, seed: int):
    """Random weights for configuration ``c`` in the serving engine's tree
    layout, made on the device. Matmul weights N(0, 1/fan_in); norm scales
    1 + N(0, 0.02^2); conv weights N(0, 1/d_conv), conv bias N(0, 0.02^2);
    Mamba2's conventions for dt (log-uniform in [time_step_min,
    time_step_max], floored at time_step_floor, stored as its inverse
    softplus), A_log = log U[1, 16] and D = 1."""
    d = dims(c)
    D, di, C, H, K = d["D"], d["di"], d["C"], d["H"], d["K"]
    dt = jnp.dtype(c["torch_dtype"])
    f32 = jnp.float32
    t_min, t_max = c["time_step_min"], c["time_step_max"]

    def normal(key, shape, scale, dtype, mean=0.0):
        return (mean + jax.random.normal(key, shape, f32) * scale).astype(dtype)

    def norm(key, width):
        return {"scale": normal(key, (width,), 0.02, f32, mean=1.0)}

    def mamba(key):
        ks = jax.random.split(key, 9)
        step = jnp.exp(jax.random.uniform(ks[5], (H,), f32)
                       * (math.log(t_max) - math.log(t_min))
                       + math.log(t_min))
        step = jnp.maximum(step, c["time_step_floor"])
        return {
            "norm": norm(ks[0], D),
            "mixer": {
                "in_proj": normal(ks[1], (D, di + C + H), D ** -0.5, dt),
                "conv_w": normal(ks[2], (K, C), K ** -0.5, dt),
                "conv_b": normal(ks[3], (C,), 0.02, dt),
                "A_log": jnp.log(jax.random.uniform(ks[4], (H,), f32,
                                                    1.0, 16.0)),
                "D": jnp.ones((H,), f32),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "gate_norm": normal(ks[6], (di,), 0.02, f32, mean=1.0),
                "out_proj": normal(ks[7], (di, D), di ** -0.5, dt)}}

    def shared(key):
        ks = jax.random.split(key, 8)
        A, F = d["A"], d["F"]
        return {"norm_in": norm(ks[0], 2 * D),
                "attn": {"wq": normal(ks[1], (2 * D, A), (2 * D) ** -0.5, dt),
                         "wk": normal(ks[2], (2 * D, A), (2 * D) ** -0.5, dt),
                         "wv": normal(ks[3], (2 * D, A), (2 * D) ** -0.5, dt),
                         "wo": normal(ks[4], (A, D), A ** -0.5, dt)},
                "norm_ff": norm(ks[5], D),
                "mlp": {"w_gate_up": normal(ks[6], (D, 2 * F), D ** -0.5, dt),
                        "w_down": normal(ks[7], (F, D), F ** -0.5, dt)}}

    def use(key):
        ks = jax.random.split(key, 3)
        r, F = d["r"], d["F"]
        return {"lora_a": normal(ks[0], (D, r), D ** -0.5, dt),
                "lora_b": normal(ks[1], (r, 2 * F), r ** -0.5, dt),
                "linear": normal(ks[2], (D, D), D ** -0.5, dt)}

    gs = groups(c)
    n_use = len(c["hybrid_layer_ids"])

    @jax.jit
    def make(key):
        ke, kn, kl, ks, ku = jax.random.split(key, 5)
        # lax.map draws one layer at a time: only that layer's float32
        # draws are live beside the stacked result
        layers = [jax.lax.map(mamba, jax.random.split(k, b - a))
                  for (a, b, _), k in zip(gs, jax.random.split(kl, len(gs)))]
        return {"embed": {"tok": normal(ke, (d["V"], D), D ** -0.5, dt)},
                "final_norm": norm(kn, D),
                "layers": layers,
                "shared": [shared(k) for k in jax.random.split(ks, d["M"])],
                "uses": [use(k) for k in jax.random.split(ku, n_use)]}

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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


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
    di, G, N, H, P = d["di"], d["G"], d["N"], d["H"], d["P"]
    heads, hd, V = d["heads"], d["hd"], d["V"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    scale = (hd / 2) ** -0.5
    head_group = np.arange(H) // (H // G)

    def mamba(x, lp, t):
        """x + Mamba2(RMSNorm(x + t)) over x (T, D)."""
        m = lp["mixer"]
        T = x.shape[0]
        u = _rms(x + t, lp["norm"]["scale"], eps)
        zxbcdt = _mm(u, m["in_proj"], fp8)
        z, xbc, dtl = (zxbcdt[:, :di], zxbcdt[:, di:di + d["C"]],
                       zxbcdt[:, di + d["C"]:])
        w = m["conv_w"].astype(jnp.float32)
        xp = jnp.pad(xbc, ((d["K"] - 1, 0), (0, 0)))
        conv = sum(xp[k:k + T] * w[k] for k in range(d["K"]))
        xbc = jax.nn.silu(conv + m["conv_b"].astype(jnp.float32))
        xs = xbc[:, :di].reshape(T, H, P)
        Bh = xbc[:, di:di + G * N].reshape(T, G, N)[:, head_group]
        Ch = xbc[:, di + G * N:].reshape(T, G, N)[:, head_group]
        dt = jax.nn.softplus(dtl + m["dt_bias"])              # (T, H)
        A = -jnp.exp(m["A_log"])

        def step(s, inp):
            x_t, dt_t, b_t, c_t = inp
            s = (s * jnp.exp(dt_t * A)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HIGHEST)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                            (xs, dt, Bh, Ch))
        y = (y + m["D"][:, None] * xs).reshape(T, di) * jax.nn.silu(z)
        y = _rms(y.reshape(T, G, di // G), 1.0, eps).reshape(T, di)
        return x + _mm(y * m["gate_norm"], m["out_proj"], fp8)

    def attention(q, k, v, pos):
        """q, k, v (T, heads, hd): causal, in query blocks."""
        T = q.shape[0]
        outs = []
        for s0 in range(0, T, Q_BLOCK):
            sc = jnp.einsum("shd,thd->hst", q[s0:s0 + Q_BLOCK], k,
                            precision=HIGHEST) * scale
            causal = pos[None, :] <= pos[s0:s0 + Q_BLOCK, None]
            w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
            o = jnp.einsum("hst,thd->shd", w, v, precision=HIGHEST)
            outs.append(o.reshape(-1, heads * hd))
        return jnp.concatenate(outs, 0)

    def shared(x, x0, bp, up):
        """The shared block's output t (no residual)."""
        T = x.shape[0]
        pos = jnp.arange(T)
        a = bp["attn"]
        u = _rms(jnp.concatenate([x, x0], -1), bp["norm_in"]["scale"], eps)
        q = _rope(_mm(u, a["wq"], fp8).reshape(T, heads, hd), pos, theta)
        k = _rope(_mm(u, a["wk"], fp8).reshape(T, heads, hd), pos, theta)
        v = _mm(u, a["wv"], fp8).reshape(T, heads, hd)
        o = _mm(attention(q, k, v, pos), a["wo"], fp8)
        m = _rms(o, bp["norm_ff"]["scale"], eps)
        gu = (_mm(m, bp["mlp"]["w_gate_up"], fp8)
              + _mm(_mm(m, up["lora_a"], fp8), up["lora_b"], fp8))
        g, upv = jnp.split(gu, 2, axis=-1)
        h = jax.nn.gelu(g, approximate=False) * upv
        return _mm(_mm(h, bp["mlp"]["w_down"], fp8), up["linear"], fp8)

    def run(x, stack, t):
        """A group: its first layer takes t, the rest none."""
        first = jax.tree.map(lambda a: a[0], stack)
        rest = jax.tree.map(lambda a: a[1:], stack)
        x = mamba(x, first, t)
        x, _ = jax.lax.scan(lambda h, lp: (mamba(h, lp, 0.0), None), x,
                            rest)
        return x

    gs = groups(c)

    @jax.jit
    def stats(w, tokens, chosen):
        x0 = jnp.take(w["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
        x = x0
        for (_, _, use), stack in zip(gs, w["layers"]):
            t = 0.0
            if use >= 0:
                t = shared(x, x0, w["shared"][use % d["M"]], w["uses"][use])
            x = run(x, stack, t)
        h = _rms(x, w["final_norm"]["scale"], eps)
        T = tokens.shape[0]
        best = jnp.full((T,), -jnp.inf, jnp.float32)
        arg = jnp.zeros((T,), jnp.int32)
        picked = jnp.zeros(chosen.shape, jnp.float32)
        emb = w["embed"]["tok"]
        for v0 in range(0, V, V_BLOCK):
            lg = _mm(h, emb[v0:v0 + V_BLOCK].T, fp8)
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

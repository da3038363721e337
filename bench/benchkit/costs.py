"""Operations and bytes a dense decoder LM needs, from its published config.

These count what the algorithm needs, not what a program happens to do:
a causal prefill attends to earlier positions only, a decode step reads
each weight once and the keys and values of the live positions, and a
prefill computes the head for its last position only.
"""
from __future__ import annotations

from typing import Dict, Iterable

WEIGHT_BYTES = 2                # bfloat16
KV_BYTES = 2


def _d(c: Dict):
    D, H = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], D, H, c["num_key_value_heads"],
            c.get("head_dim", D // H), c["intermediate_size"], c["vocab_size"])


def mlp_mats(c: Dict) -> int:
    """2 for a plain MLP, 3 for a gated one."""
    return 3 if c["hidden_act"] in ("silu", "swiglu", "geglu") else 2


def layer_matmul_params(c: Dict) -> int:
    L, D, H, KV, hd, F, V = _d(c)
    return 2 * D * H * hd + 2 * D * KV * hd + mlp_mats(c) * D * F


def weight_bytes(c: Dict) -> int:
    """All served weights: layers, embedding, head, biases and norms."""
    L, D, H, KV, hd, F, V = _d(c)
    heads = 1 if c.get("tie_word_embeddings") else 2
    biases = L * (H * hd + 2 * KV * hd) if c.get("use_bias") else 0
    norms = (2 * L + 1) * 2 * D          # scale and offset, float32
    return (L * layer_matmul_params(c) + heads * V * D + biases) \
        * WEIGHT_BYTES + norms * 4


def kv_bytes_per_token(c: Dict) -> int:
    L, D, H, KV, hd, F, V = _d(c)
    return L * 2 * KV * hd * KV_BYTES


def token_matmul_flops(c: Dict) -> int:
    """Weight-matmul FLOPs of one token through the layers and the head."""
    L, D, H, KV, hd, F, V = _d(c)
    return 2 * (L * layer_matmul_params(c) + D * V)


def attn_flops(c: Dict, attended: int) -> int:
    """QK and AV FLOPs of one query over ``attended`` positions."""
    L, D, H, KV, hd, F, V = _d(c)
    return 4 * L * H * hd * attended


def prefill_flops(c: Dict, S: int) -> int:
    """One causal prefill of S tokens, head at the last position only."""
    L, D, H, KV, hd, F, V = _d(c)
    body = 2 * L * layer_matmul_params(c) * S
    return body + 2 * D * V + attn_flops(c, S * (S + 1) // 2)


def decode_flops(c: Dict, attended: Iterable[int]) -> int:
    """One decode step; ``attended`` holds each decoded row's positions."""
    att = list(attended)
    return len(att) * token_matmul_flops(c) + attn_flops(c, sum(att))


def decode_bytes(c: Dict, attended: Iterable[int]) -> int:
    """One decode step: every weight but the embedding table once, plus
    the keys and values of the live positions of each decoded row."""
    L, D, H, KV, hd, F, V = _d(c)
    att = list(attended)
    w = weight_bytes(c) - V * D * WEIGHT_BYTES + len(att) * D * WEIGHT_BYTES
    return w + kv_bytes_per_token(c) * sum(att)

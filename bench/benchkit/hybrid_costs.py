"""Operations and bytes Zamba2's hybrid stack needs, from its published
config (``bench/configs/zamba2-7b.json``'s ``config``).

These count what the algorithm needs, not what a program happens to do:
a decode step reads each weight once, and each shared block's once per
use (a block's 0.67e9 bytes cannot stay on the chip between its uses,
which other layers separate), the tied embedding serving as the head; it
reads and rewrites each decoded row's recurrent state (SSM and conv) and
reads the keys and values of the live positions of each shared-block
use; the Mamba2 mixer is counted as its linear recurrence, never the
chunked SSD's extra work; a causal prefill attends to earlier
positions only and computes the head at its last position only. The
dense decoder's ``costs`` would misread this model.
"""
from __future__ import annotations

from typing import Dict, Iterable

WEIGHT_BYTES = 2                # bfloat16
NORM_BYTES = 4                  # norm scales, A_log, D and dt_bias: float32
STATE_BYTES = 4                 # the SSM state: float32
CONV_STATE_BYTES = 2            # the conv state: bfloat16, as its inputs
KV_BYTES = 2
POS_BYTES = 4                   # the position of each cached row: int32


def _d(c: Dict) -> Dict[str, int]:
    D = c["hidden_size"]
    di = c["mamba_expand"] * D
    G, N = c["mamba_ngroups"], c["mamba_d_state"]
    return {"L": c["num_hidden_layers"], "U": len(c["hybrid_layer_ids"]),
            "M": c["num_mem_blocks"], "D": D, "di": di, "N": N,
            "H": c["n_mamba_heads"], "P": c["mamba_headdim"],
            "K": c["mamba_d_conv"], "C": di + 2 * G * N,
            "A": c["attention_hidden_size"],
            "heads": c["num_attention_heads"], "hd": c["attention_head_dim"],
            "kv": c["num_key_value_heads"], "F": c["ffn_hidden_size"],
            "r": c["adapter_rank"], "V": c["vocab_size"]}


def mamba_matmul_params(c: Dict) -> int:
    """in_proj (to z, x|B|C and dt) and out_proj of one Mamba2 layer."""
    d = _d(c)
    return d["D"] * (d["di"] + d["C"] + d["H"]) + d["di"] * d["D"]


def use_matmul_params(c: Dict) -> int:
    """The matmul weights one use of a shared block runs through: q/k/v
    over [h, x0], o, gate/up, the use's LoRA, down and the use's linear."""
    d = _d(c)
    D, A, F, r = d["D"], d["A"], d["F"], d["r"]
    kv_width = d["kv"] * d["hd"]
    return (2 * D * (A + 2 * kv_width) + A * D + D * 2 * F
            + r * (D + 2 * F) + F * D + D * D)


def _block_bytes(c: Dict) -> int:
    """One shared block: its norms and its attention and MLP weights."""
    d = _d(c)
    D, A, F = d["D"], d["A"], d["F"]
    kv_width = d["kv"] * d["hd"]
    return (2 * D * (A + 2 * kv_width) + A * D + 3 * D * F) * WEIGHT_BYTES \
        + 3 * D * NORM_BYTES


def weight_bytes(c: Dict) -> int:
    """All served weights: Mamba2 layers, shared blocks, each use's
    adapter and linear, the embedding and the norms."""
    d = _d(c)
    D, di, H, K, C, F, r = (d["D"], d["di"], d["H"], d["K"], d["C"],
                            d["F"], d["r"])
    mamba = (mamba_matmul_params(c) + K * C + C) * WEIGHT_BYTES \
        + (3 * H + di + D) * NORM_BYTES
    use = (r * (D + 2 * F) + D * D) * WEIGHT_BYTES
    return (d["L"] * mamba + d["M"] * _block_bytes(c) + d["U"] * use
            + d["V"] * D * WEIGHT_BYTES + D * NORM_BYTES)


def streamed_weight_bytes(c: Dict) -> int:
    """Weight bytes one pass through the stack reads: every weight once,
    and each shared block once per use."""
    d = _d(c)
    return weight_bytes(c) + (d["U"] - d["M"]) * _block_bytes(c)


def state_bytes_per_seq(c: Dict) -> int:
    """One sequence's recurrent state over all layers: the SSM state and
    the conv's last K-1 inputs."""
    d = _d(c)
    return d["L"] * (d["H"] * d["P"] * d["N"] * STATE_BYTES
                     + (d["K"] - 1) * d["C"] * CONV_STATE_BYTES)


def kv_bytes_per_token(c: Dict) -> int:
    """Keys and values of one position over every shared-block use."""
    d = _d(c)
    return d["U"] * 2 * d["kv"] * d["hd"] * KV_BYTES


def slot_write_bytes(c: Dict, S: int) -> Dict[str, int]:
    """What one admission writes into the decode cache after a prefill of
    S tokens: the whole recurrent state, and the keys, values and
    positions of S rows of every use."""
    d = _d(c)
    return {"state_bytes": state_bytes_per_seq(c),
            "kv_bytes": S * (kv_bytes_per_token(c) + d["U"] * POS_BYTES)}


def token_matmul_flops(c: Dict) -> int:
    """Weight-matmul FLOPs of one token through every layer and use."""
    d = _d(c)
    return 2 * (d["L"] * mamba_matmul_params(c) + d["U"] * use_matmul_params(c))


def recurrence_flops(c: Dict) -> int:
    """One token's conv and SSM recurrence over every layer: the conv's
    K taps per channel; per head the state's decay, its rank-one update
    (dt x B^T) and the read-out s C."""
    d = _d(c)
    return d["L"] * (2 * d["K"] * d["C"] + 5 * d["H"] * d["P"] * d["N"])


def attn_flops(c: Dict, attended: int) -> int:
    """QK and AV FLOPs of one query over ``attended`` positions, every use."""
    d = _d(c)
    return 4 * d["U"] * d["heads"] * d["hd"] * attended


def head_flops(c: Dict) -> int:
    d = _d(c)
    return 2 * d["D"] * d["V"]


def prefill_flops(c: Dict, S: int) -> int:
    """One causal prefill of S tokens, head at the last position only."""
    return ((token_matmul_flops(c) + recurrence_flops(c)) * S
            + attn_flops(c, S * (S + 1) // 2) + head_flops(c))


def prefill_bytes(c: Dict, S: int) -> int:
    """One prefill: the weights a pass reads, the S rows of keys and
    values and the final recurrent state it hands to the cache."""
    return (streamed_weight_bytes(c) + S * kv_bytes_per_token(c)
            + state_bytes_per_seq(c))


def decode_flops(c: Dict, attended: Iterable[int]) -> int:
    """One decode step; ``attended`` holds each decoded row's positions."""
    att = list(attended)
    per_row = token_matmul_flops(c) + recurrence_flops(c) + head_flops(c)
    return len(att) * per_row + attn_flops(c, sum(att))


def decode_bytes(c: Dict, attended: Iterable[int]) -> int:
    """One decode step: the weights a pass reads (the embedding table is
    the head), each decoded row's state read and rewritten, and the keys
    and values of each row's live positions."""
    att = list(attended)
    return (streamed_weight_bytes(c) + 2 * len(att) * state_bytes_per_seq(c)
            + kv_bytes_per_token(c) * sum(att))

"""Zamba2-7B [arXiv:2411.15242]: 81 Mamba2 layers and two shared
attention+MLP blocks.

Published config: https://huggingface.co/Zyphra/Zamba2-7B-Instruct
(``config.json``).  Each Mamba2 layer has 112 heads of 64 over d_inner
7168, state 64 in 2 groups, conv width 4 and a gated RMSNorm per group.
Before each of the 13 ``hybrid_layer_ids`` a shared block runs, blocks A
and B in turn: RMSNorm over [h, x0] (7168 wide), 32-head attention with
head_dim 224 and scale (224/2)^-1/2, RMSNorm, a GELU-gated MLP whose
gate/up projection takes that use's rank-128 LoRA, then that use's
3584 x 3584 linear; the result joins the Mamba layer's input, not the
residual stream.  Tied embeddings; 7.357e9 parameters.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b", family="hybrid",
    num_layers=81, d_model=3584, num_heads=32, num_kv_heads=32, head_dim=224,
    attn_scale=112 ** -0.5,
    d_ff=14336, vocab_size=32000, act="gelu", tie_embeddings=True,
    norm_eps=1e-5, rope_theta=10_000.0,
    ssm_state=64, ssm_headdim=64, ssm_expand=2, ssm_chunk=256, ssm_ngroups=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2, adapter_rank=128,
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct; "
           "arXiv:2411.15242",
)

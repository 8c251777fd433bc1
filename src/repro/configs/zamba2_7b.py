"""zamba2-7b [hybrid]: 81 Mamba2 layers (d=3584, 112 heads x 64, 2 B/C
groups, state 64, conv bias); 13 of them also take a call of one of TWO
weight-shared attention+MLP blocks, alternating (32H x 224 over the 2d
concatenation [hidden; embedding], gated-GELU d_ff=14336), each call with its
own rank-128 MLP adapter and d x d output projection.
[huggingface.co/Zyphra/Zamba2-7B-Instruct config.json; arXiv:2411.15242]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2_7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
    d_ff=14_336, vocab_size=32_000, act="gelu_exact", rope_theta=10_000.0,
    ssm_state=64, ssm_conv=4, ssm_expand=2, ssm_head_dim=64, ssm_groups=2,
    ssm_conv_bias=True, ssm_chunk=256,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    n_mem_blocks=2, adapter_rank=128,
)

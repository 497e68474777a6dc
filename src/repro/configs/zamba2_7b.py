"""zamba2-7b — hybrid: Mamba2 backbone + two shared attention blocks
[arXiv:2411.15242; Zyphra/Zamba2-7B-Instruct config.json].

81 Mamba2 layers (2 B/C groups, gated RMSNorm normalised per group).
Before the mixer of each layer in ``hybrid_layers`` a shared block reads
``concat[h, embedding]`` (width 2 x 3584): RMSNorm, attention with 32
heads of 224 and rotary embedding, RMSNorm, a GELU MLP whose gate/up
projection adds a rank-128 LoRA of that call; a linear of that call maps
its output into the mixer's input.  The two blocks are used in turn.
Sub-quadratic -> runs long_500k."""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    arch_id="zamba2-7b",
    family="mamba_hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab=32000,
    rope_theta=10_000.0,
    tie_embeddings=True,
    ssm_state=64,
    ssm_conv=4,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_groups=2,
    hybrid_layers=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    shared_blocks=2,
    adapter_rank=128,
    norm_eps=1e-5,
    sub_quadratic=True,
))

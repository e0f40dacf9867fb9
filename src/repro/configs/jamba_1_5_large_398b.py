"""jamba-1.5-large-398b [hybrid] — 72L d_model=8192 64H (GQA kv=8)
d_ff=24576, MoE 16 experts top-2 on every other layer (softmax then top-k,
not renormalised); Mamba+attention 7:1 interleave (period-8 blocks,
attention at offset 4). Attention has no positional encoding; the Mamba
mixer RMS-normalises its time-step input, B and C. ~398B total params.
[arXiv:2403.19887; hf]"""
from repro.configs.base import AttnConfig, MambaConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="jamba-1.5-large-398b",
    family="hybrid",
    n_layers=72,
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    d_ff=24576,
    vocab_size=65536,
    block_pattern=("mamba",) * 4 + ("attn",) + ("mamba",) * 3,
    mlp="gated_silu",
    attn=AttnConfig(pattern=("full",), rope_theta=None),
    moe=MoEConfig(n_experts=16, top_k=2, period=2, router_norm_topk=False),
    mamba=MambaConfig(d_state=16, d_conv=4, expand=2, chunk=256),
    norm="rmsnorm",
    max_seq_len=262144,
).validate()

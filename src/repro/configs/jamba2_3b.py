"""jamba2-3b [hybrid] — 28L d_model=2560 20H (one KV head, head_dim 128)
d_ff=8192, vocab 65536, tied embeddings. Mamba-1 and attention 13:1
(period 14, attention at offset 7: layers 7 and 21); dense gated-SiLU MLP on
every layer (one expert). Attention has no positional encoding; the Mamba
mixer RMS-normalises its time-step input, B and C.
[hf:ai21labs/AI21-Jamba2-3B config.json; arXiv:2403.19887]"""
from repro.configs.base import AttnConfig, MambaConfig, ModelConfig

CONFIG = ModelConfig(
    name="jamba2-3b",
    family="hybrid",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    d_ff=8192,
    vocab_size=65536,
    block_pattern=("mamba",) * 7 + ("attn",) + ("mamba",) * 6,
    mlp="gated_silu",
    attn=AttnConfig(pattern=("full",), rope_theta=None),
    mamba=MambaConfig(d_state=16, d_conv=4, expand=2, dt_rank=160, chunk=256),
    norm="rmsnorm",
    norm_eps=1e-6,
    tie_embeddings=True,
    max_seq_len=262144,
).validate()

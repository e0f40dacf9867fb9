"""Names of the program's layers, one ``jax.named_scope`` at each boundary.

The scope path lands in the ``op_name`` metadata of every HLO instruction
built inside it, through the backward pass (``transpose(jvp(...))``) and
rematerialised recompute alike, so a device op maps to its layer through
the compiled program's text. Scopes change metadata only, no instruction.
"""

EMBED = "embed"           # token embedding
ATTN = "attn"             # first norm, attention with its LoRA
RECURRENT = "recurrent"   # mamba block, or rwkv time mix (around wkv_fused)
SSM_SCAN = "ssm_scan"     # inside a mamba block: causal conv, selective scan
MLP = "mlp"               # second norm, dense MLP or MoE; rwkv channel mix
HEAD = "head"             # final norm and unembedding
LOSS = "loss"             # cross entropy over the logits
OPTIMIZER = "optimizer"   # gradient clip and AdamW update

LAYERS = (EMBED, ATTN, RECURRENT, SSM_SCAN, MLP, HEAD, LOSS, OPTIMIZER)

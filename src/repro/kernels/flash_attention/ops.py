"""Public wrappers: (B, T, H, D) layout in and out, GQA folding, padding."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_bwd, flash_fwd

# The training kernels' blocks, (q, kv), chosen on a v5e at G = Hq/Hkv = 4
# and D = 128. A kernel folds the G query heads of a KV head into the lanes
# of its tiles, (kv block, G * q block), so the q block is halved until
# G * q block (scaled by D / 128 past D = 128) is no more than at G = 4;
# where even MIN_BLOCK is too wide the kernels do not apply. Each block is
# then capped at T and halved until it divides T. Blocks are lanes of the
# transposed scores, so T must be a multiple of MIN_BLOCK.
MIN_BLOCK = 128
FWD_BLOCKS = (512, 512)
DKV_BLOCKS = (256, 512)
DQ_BLOCKS = (512, 512)
_TUNED_GROUPS = 4


def _fit(n, b):
    b = min(b, n)
    while n % b:
        b //= 2
    return b


def causal_blocks(T, G, D):
    """The (forward, dK/dV, dQ) blocks, each (q, kv), for self-attention
    over T tokens with G query heads of width D per KV head; None where
    the kernels do not apply."""
    if T % MIN_BLOCK:
        return None
    out = []
    for bq, bk in (FWD_BLOCKS, DKV_BLOCKS, DQ_BLOCKS):
        lanes = bq * _TUNED_GROUPS * 128 // max(D, 128)
        while G * bq > lanes and bq > MIN_BLOCK:
            bq //= 2
        if G * bq > lanes:
            return None
        out.append((_fit(T, bq), _fit(T, bk)))
    return tuple(out)


def _heads_major(x, groups):
    """(B, T, Hkv*G, D) -> (B*Hkv, G, T, D)."""
    B, T, H, D = x.shape
    x = x.reshape(B, T, H // groups, groups, D).transpose(0, 2, 3, 1, 4)
    return x.reshape(B * (H // groups), groups, T, D)


def _seq_major(x, batch):
    """(B*Hkv, G, T, D) -> (B, T, Hkv*G, D)."""
    BH, G, T, D = x.shape
    x = x.reshape(batch, BH // batch, G, T, D).transpose(0, 3, 1, 2, 4)
    return x.reshape(batch, T, (BH // batch) * G, D)


def flash_attention(q, k, v, q_pos, kv_pos, *, window=None, softcap=None,
                    block_q=128, block_kv=128, interpret=False):
    """q (B, T, Hq, D); k/v (B, S, Hkv, D); positions (B, T)/(B, S)."""
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    bq = min(block_q, T)
    bk = min(block_kv, S)
    pad_t = (-T) % bq
    pad_s = (-S) % bk
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_t)))
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_s)), constant_values=-1)

    out, _ = flash_fwd(_heads_major(q, Hq // Hkv), _heads_major(k, 1)[:, 0],
                       _heads_major(v, 1)[:, 0], q_pos, kv_pos,
                       window=window, softcap=softcap, block_q=bq,
                       block_kv=bk, interpret=interpret)
    return _seq_major(out, B)[:, :T]


def causal_attention_fwd(q, k, v, q_pos, kv_pos, *, interpret=False,
                         dot_dtype=None):
    """Self-attention masked by positions (causal, -1 invalid), where
    ``causal_blocks`` applies. Returns the output (B, T, Hq, D) and the
    float32 log-sum-exp of each query's scores (B, Hq, T). ``dot_dtype``
    as in ``kernel.flash_fwd``."""
    B, T, Hq, D = q.shape
    G = Hq // k.shape[2]
    (bq, bk), _, _ = causal_blocks(T, G, D)
    oh, lse = flash_fwd(_heads_major(q, G), _heads_major(k, 1)[:, 0],
                        _heads_major(v, 1)[:, 0], q_pos, kv_pos, block_q=bq,
                        block_kv=bk, interpret=interpret, dot_dtype=dot_dtype)
    return _seq_major(oh, B), lse.reshape(B, Hq, T)


def causal_attention_bwd(q, k, v, q_pos, kv_pos, out, lse, dout, *,
                         interpret=False, dot_dtype=None):
    """Gradients (dq, dk, dv), in the layouts and dtypes of q, k, v, from
    ``causal_attention_fwd``'s output and log-sum-exp."""
    B, T, Hq, D = q.shape
    G = Hq // k.shape[2]
    _, dkv_blocks, dq_blocks = causal_blocks(T, G, D)
    oh, doh = _heads_major(out, G), _heads_major(dout, G)
    di = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32), axis=-1)
    lse = lse.reshape(-1, G, 1, T)
    dq, dk, dv = flash_bwd(
        _heads_major(q, G), _heads_major(k, 1)[:, 0],
        _heads_major(v, 1)[:, 0], q_pos, kv_pos, doh, lse,
        di[:, :, None, :], dkv_blocks=dkv_blocks, dq_blocks=dq_blocks,
        interpret=interpret, dot_dtype=dot_dtype)
    return (_seq_major(dq, B).astype(q.dtype),
            _seq_major(dk[:, None], B).astype(k.dtype),
            _seq_major(dv[:, None], B).astype(v.dtype))

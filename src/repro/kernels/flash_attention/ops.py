"""Public wrapper: (B, T, H, D) layout in/out, GQA folding, padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def flash_attention(q, k, v, q_pos, kv_pos, *, window=None, softcap=None,
                    block_q=128, block_kv=128, interpret=False):
    """q (B, T, Hq, D); k/v (B, S, Hkv, D); positions (B, T)/(B, S)."""
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    bq = min(block_q, T)
    bk = min(block_kv, S)
    pad_t = (-T) % bq
    pad_s = (-S) % bk
    group = Hq // Hkv

    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    qp = jnp.repeat(q_pos, Hq, axis=0).reshape(B * Hq, T)
    kp = jnp.repeat(kv_pos, Hkv, axis=0).reshape(B * Hkv, S)
    if pad_t:
        qf = jnp.pad(qf, ((0, 0), (0, pad_t), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, pad_t)))
    if pad_s:
        kf = jnp.pad(kf, ((0, 0), (0, pad_s), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_s), (0, 0)))
        kp = jnp.pad(kp, ((0, 0), (0, pad_s)), constant_values=-1)

    out = flash_attention_kernel(qf, kf, vf, qp, kp, window=window,
                                 softcap=softcap, block_q=bq, block_kv=bk,
                                 interpret=interpret)
    if pad_t:
        out = out[:, :T]
    return out.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)

"""Pallas TPU kernel: fused score+softmax+V attention (Atleus's DYNAMIC
engine / systolic-array computation, SS IV.A ref [39]).

Output-stationary dataflow: the (bq, D) output accumulator and the running
(max, sum) statistics live in VMEM scratch across the KV grid dimension
while K/V blocks stream from HBM — the direct analogue of the paper's OS
systolic mapping for dynamic-operand matmuls. Supports GQA via the kv-head
index map, causal/sliding masks from explicit position vectors, and gemma2
logit softcapping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, out_ref,
                 acc_ref, m_ref, l_ref, *, n_kv, scale, window, softcap):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale      # (bq, D)
    k = k_ref[0].astype(jnp.float32)              # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qp = qpos_ref[0]                              # (bq, 1)
    kp = kpos_ref[0]                              # (1, bk)
    mask = (kp <= qp) & (kp >= 0)
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(kb == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "block_q",
                                             "block_kv", "interpret"))
def flash_attention_kernel(q, k, v, q_pos, kv_pos, *, window=None,
                           softcap=None, block_q=128, block_kv=128,
                           interpret=False):
    """q (BH, T, D); k/v (BHkv, S, D); q_pos (BH, T); kv_pos (BHkv, S).
    BH == B*Hq, BHkv == B*Hkv with Hq grouped per kv head (GQA): program
    (bh, ...) reads kv block bh // group.

    Positions enter as a (BH, T, 1) column and a (BHkv, 1, S) row: the TPU
    lowering needs the last two block dims (8, 128)-aligned or full, and
    the column/row layout also lets the mask broadcast without a relayout."""
    BH, T, D = q.shape
    BHkv, S, _ = k.shape
    group = BH // BHkv
    assert T % block_q == 0 and S % block_kv == 0
    n_kv = S // block_kv
    grid = (BH, T // block_q, 1, n_kv)
    scale = D ** -0.5

    kern = functools.partial(_attn_kernel, n_kv=n_kv, scale=scale,
                             window=window, softcap=softcap)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, i, j, kb: (b, i, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b, i, j, kb: (b // group, 0, kb)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j, kb: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j, kb: (b // group, kb, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j, kb: (b // group, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j, kb: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos[:, :, None], kv_pos[:, None, :], q, k, v)

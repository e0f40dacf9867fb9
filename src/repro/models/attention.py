"""Attention: the DYNAMIC-engine computation (Atleus MHA-2/MHA-3).

Three interchangeable implementations of the fused score+softmax+V step
(the paper adopts FlashAttention-2-style fusion, SS IV.A):

  * ``ref``     — materialized scores; oracle for tests & decode (T_q == 1).
  * ``blocked`` — lax.scan over KV blocks with running (max, sum, acc);
                  pure-JAX flash attention used for train/prefill lowering.
  * ``banded``  — sliding-window prefill: gathers only the KV band each
                  Q block can see (FLOPs scale with window, not seq —
                  8x reduction at 32k/w4096), then runs ``blocked`` inside.
  * pallas      — TPU kernel (repro.kernels.flash_attention), selected via
                  ``impl='pallas'``; checked against ``ref`` in interpret
                  mode and compiled for v5e in tests/test_tpu_compile.py.
  * fused       — the same kernels forward and backward (``fused_attention``),
                  taken in place of ``blocked`` wherever they apply: lowered
                  for a TPU, unsharded causal self-attention (T == S, at a
                  length, head ratio and width ``causal_blocks`` takes)
                  without window or softcap.

Supports GQA (any q/kv head ratio), causal masking via explicit position
arrays (required under sequence-parallel Q sharding), sliding windows,
gemma2 logit softcapping, and invalid-slot masking for ring caches.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import hetero
from repro.core.lora import lora_delta, lora_scale
from repro.core.noise import NoiseConfig
from repro.kernels.flash_attention import ops as fa_ops
from repro.models import layers

Array = jax.Array

NEG_INF = -1e30

# Cache-leaf taxonomy under the paged serving layout. POOL_LEAVES are
# block-table addressed (full attention KV): a rejected speculative suffix
# rolls back by rewinding the host-side write cursor alone. The sliding
# ring keeps the last W tokens *keyed by slot row* — SLOT_STATE_LEAVES
# names those per-slot arrays so the serving ``SlotStateArena`` can
# snapshot / select-restore / zero them by slot id around verify chunks.
SLOT_STATE_LEAVES = ("k", "v")
POOL_LEAVES = ("kp", "vp")


def _mask(q_pos: Array, kv_pos: Array, window: Optional[int]) -> Array:
    """(B, Tq, S) bool. kv_pos == -1 marks invalid (unwritten ring slots)."""
    m = kv_pos[:, None, :] <= q_pos[:, :, None]
    m &= kv_pos[:, None, :] >= 0
    if window is not None:
        m &= (q_pos[:, :, None] - kv_pos[:, None, :]) < window
    return m


def _softcap(scores: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return scores
    hetero.record_nonlinear(scores.size)
    return cap * jnp.tanh(scores / cap)


def ref_attention(q: Array, k: Array, v: Array, q_pos: Array, kv_pos: Array,
                  *, window: Optional[int] = None,
                  softcap: Optional[float] = None, sharder=None) -> Array:
    """q (B,T,Hq,D); k/v (B,S,Hkv,D) -> (B,T,Hq,D). f32 softmax.

    Decode with a head_dim-sharded KV cache: the scores constraint forces
    GSPMD to psum partial scores (tens of MB) instead of all-gathering the
    whole cache over tp (tens of GB/step)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, T, Hkv, G, D) * (D ** -0.5)
    s = hetero.dynamic_einsum("bthgd,bshd->bhgts", qg, k,
                              preferred_element_type=jnp.float32)
    if sharder is not None:
        s = sharder(s, "attn_scores")
    s = _softcap(s.astype(jnp.float32), softcap)
    m = _mask(q_pos, kv_pos, window)[:, None, None, :, :]
    s = jnp.where(m, s, NEG_INF)
    hetero.record_nonlinear(s.size)
    p = jax.nn.softmax(s, axis=-1)
    o = hetero.dynamic_einsum("bhgts,bshd->bthgd", p.astype(v.dtype), v)
    return o.reshape(B, T, Hq, D)


def _blocked_kv(k, v, kv_pos, block_kv):
    B, S, Hkv, D = k.shape
    if S % block_kv != 0:
        pad = block_kv - S % block_kv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        S += pad
    nb = S // block_kv
    kb = k.reshape(B, nb, block_kv, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_kv, Hkv, D).transpose(1, 0, 2, 3, 4)
    pb = kv_pos.reshape(B, nb, block_kv).transpose(1, 0, 2)
    return kb, vb, pb


def _flash_fwd_impl(q, k, v, q_pos, kv_pos, window, softcap, block_kv,
                    sharder=None, folded=False):
    with jax.named_scope("flash_fused"):
        return _flash_fwd_scoped(q, k, v, q_pos, kv_pos, window, softcap,
                                 block_kv, sharder, folded)


def _flash_fwd_scoped(q, k, v, q_pos, kv_pos, window, softcap, block_kv,
                      sharder=None, folded=False):
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    sh = _flash_sharder(sharder, folded)
    qg = sh((q.reshape(B, T, Hkv, G, D) * (D ** -0.5)).astype(q.dtype), "flash_q")
    kb, vb, pb = _blocked_kv(k, v, kv_pos, block_kv)
    kb, vb, pb = sh(kb, "flash_kv"), sh(vb, "flash_kv"), sh(pb, "flash_pb")

    m0 = sh(jnp.full((B, Hkv, G, T), NEG_INF, jnp.float32), "flash_ml")
    l0 = sh(jnp.zeros((B, Hkv, G, T), jnp.float32), "flash_ml")
    a0 = sh(jnp.zeros((B, T, Hkv, G, D), jnp.float32), "flash_acc")

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, pblk = blk
        s = hetero.dynamic_einsum("bthgd,bshd->bhgts", qg, kblk,
                                  preferred_element_type=jnp.float32)
        s = _softcap(s.astype(jnp.float32), softcap)
        msk = _mask(q_pos, pblk, window)[:, None, None, :, :]
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(msk, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None]
        acc = acc + hetero.dynamic_einsum(
            "bhgts,bshd->bthgd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    with hetero.repeated(kb.shape[0]):
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))          # (B,Hkv,G,T)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = out.reshape(B, T, Hq, D).astype(q.dtype)
    return out, lse


def _flash_sharder(sharder, folded):
    if sharder is None:
        return lambda x, n: x
    suf = "_f" if folded else ""
    return lambda x, n: sharder(x, n + suf)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_pos, kv_pos, window, softcap, block_kv, sharder=None,
           folded=False):
    return _flash_fwd_impl(q, k, v, q_pos, kv_pos, window, softcap, block_kv,
                           sharder, folded)[0]


def _flash_fwd(q, k, v, q_pos, kv_pos, window, softcap, block_kv,
               sharder=None, folded=False):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, kv_pos, window, softcap,
                               block_kv, sharder, folded)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _flash_bwd(window, softcap, block_kv, sharder, folded, res, dout):
    """FlashAttention-2 backward: recompute scores blockwise from (q,k,v,lse);
    nothing O(T*S) is ever materialized (the paper's fused score+softmax,
    SS IV.A ref [39], including the backward pass for LoRA fine-tuning)."""
    with jax.named_scope("flash_fused"):
        return _flash_bwd_scoped(window, softcap, block_kv, sharder, folded,
                                 res, dout)


def _flash_bwd_scoped(window, softcap, block_kv, sharder, folded, res, dout):
    q, k, v, q_pos, kv_pos, out, lse = res
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    S = k.shape[1]
    c = D ** -0.5
    sh = _flash_sharder(sharder, folded)
    qg = sh((q.reshape(B, T, Hkv, G, D) * c).astype(q.dtype), "flash_q")
    kb, vb, pb = _blocked_kv(k, v, kv_pos, block_kv)
    kb, vb, pb = sh(kb, "flash_kv"), sh(vb, "flash_kv"), sh(pb, "flash_pb")
    do = sh(dout.reshape(B, T, Hkv, G, D), "flash_acc")
    # D_i = sum_d dout_i * out_i  (B,Hkv,G,T)
    drow = jnp.sum(do.astype(jnp.float32) * out.reshape(B, T, Hkv, G, D)
                   .astype(jnp.float32), axis=-1).transpose(0, 2, 3, 1)
    drow = sh(drow, "flash_ml")

    dq0 = sh(jnp.zeros((B, T, Hkv, G, D), jnp.float32), "flash_acc")

    def body(dq, blk):
        kblk, vblk, pblk = blk
        s = hetero.dynamic_einsum("bthgd,bshd->bhgts", qg, kblk,
                                  preferred_element_type=jnp.float32)
        s = s.astype(jnp.float32)
        if softcap is not None:
            t = jnp.tanh(s / softcap)
            sc = softcap * t
            dcap = 1.0 - jnp.square(t)
        else:
            sc = s
            dcap = None
        msk = _mask(q_pos, pblk, window)[:, None, None, :, :]
        p = jnp.where(msk, jnp.exp(sc - lse[..., None]), 0.0)
        dp = hetero.dynamic_einsum("bthgd,bshd->bhgts", do, vblk,
                                   preferred_element_type=jnp.float32)
        dv_b = hetero.dynamic_einsum("bhgts,bthgd->bshd",
                                     p.astype(do.dtype), do,
                                     preferred_element_type=jnp.float32)
        ds = p * (dp.astype(jnp.float32) - drow[..., None])
        if dcap is not None:
            ds = ds * dcap
        ds = ds.astype(q.dtype)
        dq = dq + hetero.dynamic_einsum("bhgts,bshd->bthgd", ds, kblk,
                                        preferred_element_type=jnp.float32)
        dk_b = hetero.dynamic_einsum("bhgts,bthgd->bshd", ds, qg,
                                     preferred_element_type=jnp.float32)
        return dq, (dk_b, dv_b)

    with hetero.repeated(kb.shape[0]):
        dq, (dk_s, dv_s) = jax.lax.scan(body, dq0, (kb, vb, pb))
    dq = (dq * c).reshape(B, T, Hq, D).astype(q.dtype)
    nb = dk_s.shape[0]
    dk = dk_s.transpose(1, 0, 2, 3, 4).reshape(B, nb * block_kv, Hkv, D)
    dv = dv_s.transpose(1, 0, 2, 3, 4).reshape(B, nb * block_kv, Hkv, D)
    dk = dk[:, :S].astype(k.dtype)
    dv = dv[:, :S].astype(v.dtype)
    zpos = np.zeros(q_pos.shape, jax.dtypes.float0)
    zkpos = np.zeros(kv_pos.shape, jax.dtypes.float0)
    return dq, dk, dv, zpos, zkpos


_flash.defvjp(_flash_fwd, _flash_bwd)


def blocked_attention(q: Array, k: Array, v: Array, q_pos: Array,
                      kv_pos: Array, *, window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      block_kv: int = 512, sharder=None,
                      folded: bool = False) -> Array:
    """Flash-style streaming attention with a fused custom VJP:
    O(T*S) compute, O(T + block) memory in both passes."""
    return _flash(q, k, v, q_pos, kv_pos, window, softcap, block_kv, sharder,
                  folded)


def _kernels_fwd(q, k, v, q_pos, kv_pos, interpret=False):
    """The fused kernels' forward: (out, lse), lse float32 (B, Hkv, G, T)
    as ``_flash_fwd_impl`` gives it."""
    B, T, Hq, _ = q.shape
    Hkv = k.shape[2]
    with jax.named_scope("flash_fused"):
        out, lse = fa_ops.causal_attention_fwd(q, k, v, q_pos, kv_pos,
                                               interpret=interpret)
    return out, lse.reshape(B, Hkv, Hq // Hkv, T)


def _kernels_bwd(res, dout, interpret=False):
    """(dq, dk, dv) from the residuals ``_flash_fwd`` keeps."""
    q, k, v, q_pos, kv_pos, out, lse = res
    B, T, Hq, _ = q.shape
    with jax.named_scope("flash_fused"):
        return fa_ops.causal_attention_bwd(
            q, k, v, q_pos, kv_pos, out, lse.reshape(B, Hq, T), dout,
            interpret=interpret)


def _no_pos_grads(res):
    return (np.zeros(res[3].shape, jax.dtypes.float0),
            np.zeros(res[4].shape, jax.dtypes.float0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_attention(q: Array, k: Array, v: Array, q_pos: Array,
                    kv_pos: Array, interpret: bool = False) -> Array:
    """Causal self-attention as fused Pallas kernels, forward and backward,
    where ``fa_ops.causal_blocks`` applies: scores never leave VMEM and
    (q, kv) block pairs the mask hides are skipped. ``attend`` takes the
    same kernels on a TPU through ``_causal_self_attention``."""
    return _kernels_fwd(q, k, v, q_pos, kv_pos, interpret)[0]


def _fused_fwd(q, k, v, q_pos, kv_pos, interpret):
    out, lse = _kernels_fwd(q, k, v, q_pos, kv_pos, interpret)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _fused_bwd(interpret, res, dout):
    return (*_kernels_bwd(res, dout, interpret), *_no_pos_grads(res))


fused_attention.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _causal_self_attention(q, k, v, q_pos, kv_pos, block_kv):
    """``fused_attention`` where the call is lowered for a TPU, else
    ``blocked_attention``. Each rule stages both and the lowering keeps
    one; the blocked path, traced once per rule, keeps the operation
    tally for either."""
    return _causal_fwd(q, k, v, q_pos, kv_pos, block_kv)[0]


def _causal_fwd(q, k, v, q_pos, kv_pos, block_kv):
    out, lse = jax.lax.platform_dependent(
        q, k, v, q_pos, kv_pos, tpu=_kernels_fwd,
        default=functools.partial(_flash_fwd_impl, window=None, softcap=None,
                                  block_kv=block_kv))
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _causal_bwd(block_kv, res, dout):
    dq, dk, dv = jax.lax.platform_dependent(
        res, dout, tpu=_kernels_bwd,
        default=lambda res, dout: _flash_bwd(None, None, block_kv, None,
                                             False, res, dout)[:3])
    return dq, dk, dv, *_no_pos_grads(res)


_causal_self_attention.defvjp(_causal_fwd, _causal_bwd)


def _fused_applies(q_shape, k_shape, window, softcap, sharder) -> bool:
    """Whether ``fused_attention`` can stand in for ``blocked_attention``
    on a TPU: unsharded causal self-attention without window or softcap,
    at a length, head ratio and width the kernels take."""
    (_, T, Hq, D), S = q_shape, k_shape[1]
    return (sharder is None and window is None and softcap is None
            and T == S
            and fa_ops.causal_blocks(T, Hq // k_shape[2], D) is not None)


def banded_attention(q: Array, k: Array, v: Array, q_pos: Array,
                     kv_pos: Array, *, window: int,
                     softcap: Optional[float] = None,
                     block_q: int = 2048, block_kv: int = 512,
                     sharder=None) -> Array:
    """Sliding-window attention where each Q block only touches its KV band.

    Requires T == S == len(kv) and aligned positions (prefill/train). The
    band for q block i is kv blocks [i - ceil(w/bq), i]; out-of-range blocks
    are clamped to 0 and masked via positions."""
    B, T, Hq, D = q.shape
    S = k.shape[1]
    assert T == S, "banded path is for self-attention prefill/train"
    bq = min(block_q, T)
    nqb = T // bq
    nband = -(-window // bq) + 1  # ceil(w/bq) + 1

    qb = q.reshape(B, nqb, bq, Hq, D)
    qpb = q_pos.reshape(B, nqb, bq)
    kb = k.reshape(B, nqb, bq, k.shape[2], D)
    vb = v.reshape(B, nqb, bq, v.shape[2], D)
    kpb = kv_pos.reshape(B, nqb, bq)

    idx = jnp.arange(nqb)[:, None] - jnp.arange(nband - 1, -1, -1)[None, :]
    oob = idx < 0
    idx = jnp.maximum(idx, 0)  # (nqb, nband)

    kband = jnp.take(kb, idx, axis=1)          # (B, nqb, nband, bq, Hkv, D)
    vband = jnp.take(vb, idx, axis=1)
    pband = jnp.take(kpb, idx, axis=1)         # (B, nqb, nband, bq)
    pband = jnp.where(oob[None, :, :, None], -1, pband)

    Bn = B * nqb
    kband = kband.reshape(Bn, nband * bq, k.shape[2], D)
    vband = vband.reshape(Bn, nband * bq, v.shape[2], D)
    pband = pband.reshape(Bn, nband * bq)
    qfold = qb.reshape(Bn, bq, Hq, D)
    qpfold = qpb.reshape(Bn, bq)

    out = blocked_attention(qfold, kband, vband, qpfold, pband,
                            window=window, softcap=softcap,
                            block_kv=min(block_kv, nband * bq),
                            sharder=sharder, folded=True)
    return out.reshape(B, T, Hq, D)


def attend(q, k, v, q_pos, kv_pos, *, kind: str, window: Optional[int],
           softcap: Optional[float], impl: str, block_q: int,
           block_kv: int, sharder=None) -> Array:
    window = window if kind == "sliding" else None
    T, S = q.shape[1], k.shape[1]
    if window is not None and window >= S:
        window = None   # sliding degenerates to full causal
    if impl == "pallas":
        return fa_ops.flash_attention(q, k, v, q_pos, kv_pos, window=window,
                                      softcap=softcap)
    if impl == "ref" or T == 1 or S <= block_kv:
        return ref_attention(q, k, v, q_pos, kv_pos, window=window,
                             softcap=softcap, sharder=sharder)
    if (window is not None and T == S and T % min(block_q, T) == 0
            and window >= block_q and impl in ("auto", "banded", "blocked")):
        return banded_attention(q, k, v, q_pos, kv_pos, window=window,
                                softcap=softcap, block_q=block_q,
                                block_kv=block_kv, sharder=sharder)
    if _fused_applies(q.shape, k.shape, window, softcap, sharder):
        return _causal_self_attention(q, k, v, q_pos, kv_pos, block_kv)
    return blocked_attention(q, k, v, q_pos, kv_pos, window=window,
                             softcap=softcap, block_kv=block_kv,
                             sharder=sharder)


# ---------------------------------------------------------------------------
# Paged decode: scatter the chunk into pool pages / ring slots, gather the
# visible context back through the block table, and run a normal attend().
# Padded tail tokens of a ragged chunk scatter to an out-of-bounds index and
# are DROPPED (mode="drop"), so they can never corrupt ring slots or pages.
#
# Prefix-shared pages (serve/prefix.py) need no handling here: the gather is
# purely block-table-driven, so a page mapped by several tables is simply
# read by each, and visibility (`gpos < lens + clens`) masks any resident
# tokens beyond a sharer's own length (e.g. garbage past the matched point
# in a CoW-forked tail page). Writes never target a co-held page — the
# scheduler forks it into the writer's table first.
# ---------------------------------------------------------------------------


def _paged_pool_update(pool: Array, new: Array, page_ids: Array,
                       within: Array) -> Array:
    """pool (P, Hkv, page, D); new (B, T, Hkv, D); page_ids/within (B, T).
    Invalid targets carry page_id == P (out of bounds -> dropped)."""
    B, T = new.shape[:2]
    return pool.at[page_ids.reshape(-1), :, within.reshape(-1), :].set(
        new.reshape(B * T, *new.shape[2:]).astype(pool.dtype), mode="drop")


def _paged_attend(cfg: ModelConfig, q, k, v, positions, cache, paged, *,
                  kind, softcap, impl, block_q, block_kv, sharder):
    """Decode/chunked-prefill attention against a paged or ring cache.

    q/k/v: (B, T, H, D) for the current chunk. ``paged``: block_table
    (B, nb), lens (B,), chunk_lens (B,), page_size. Returns (out, new_entry).
    """
    B, T = q.shape[0], q.shape[1]
    lens, clens = paged["lens"], paged["chunk_lens"]
    valid = jnp.arange(T)[None, :] < clens[:, None]          # (B, T)

    if "kp" in cache:                                        # full attn: pool
        page = paged["page_size"]
        bt = paged["block_table"]                            # (B, nb)
        nb = bt.shape[1]
        n_pages = cache["kp"].shape[0]
        col = positions // page
        colc = jnp.clip(col, 0, nb - 1)
        pid = jnp.take_along_axis(bt, colc, axis=1)          # (B, T)
        ok = valid & (col < nb) & (pid >= 0)
        pid = jnp.where(ok, pid, n_pages)                    # OOB -> drop
        within = positions % page
        kp = _paged_pool_update(cache["kp"], k, pid, within)
        vp = _paged_pool_update(cache["vp"], v, pid, within)
        if sharder is not None:
            # tensor-parallel serving: the pool shards head_dim on the
            # model axis, so the scatter above lands shard-local (pages /
            # within-page dims replicate) and the block-table gather below
            # stays collective-free; q aligns with the hd-sharded pool and
            # the score contraction over D psums inside attend()
            kp = sharder(kp, "paged_pool")
            vp = sharder(vp, "paged_pool")
            q = sharder(q, "paged_q")
        safe_bt = jnp.maximum(bt, 0)
        kg = kp[safe_bt]                                     # (B, nb, Hkv, pg, D)
        vg = vp[safe_bt]
        S = nb * page
        kg = kg.transpose(0, 1, 3, 2, 4).reshape(B, S, cfg.n_kv_heads, cfg.hd)
        vg = vg.transpose(0, 1, 3, 2, 4).reshape(B, S, cfg.n_kv_heads, cfg.hd)
        gpos = (jnp.arange(nb)[:, None] * page
                + jnp.arange(page)[None, :]).reshape(-1)     # (S,)
        visible = jnp.repeat(bt >= 0, page, axis=1)          # (B, S)
        end = (lens + clens)[:, None]
        kv_pos = jnp.where(visible & (gpos[None, :] < end), gpos[None, :], -1)
        new_entry = {"kp": kp, "vp": vp}
    else:                                                    # sliding: ring
        kc, vc = cache["k"], cache["v"]                      # (B, Hkv, W, D)
        W = kc.shape[2]
        # attend over [ring history ; in-chunk K/V]: the ring may not be
        # able to hold the whole chunk (T > W legal), so in-chunk tokens
        # attend each other directly and the ring supplies only history.
        i = jnp.arange(W)[None, :]
        last_hist = (lens - 1)[:, None]
        # ring slot i holds the latest position == i (mod W) <= lens-1;
        # never-written slots resolve to negative -> masked
        hist_pos = last_hist - ((last_hist - i) % W)
        kg = jnp.concatenate(
            [kc.transpose(0, 2, 1, 3).astype(q.dtype), k], axis=1)
        vg = jnp.concatenate(
            [vc.transpose(0, 2, 1, 3).astype(q.dtype), v], axis=1)
        kv_pos = jnp.concatenate(
            [hist_pos, jnp.where(valid, positions, -1)], axis=1)
        # write-back with last-wins masking: of chunk tokens sharing a ring
        # slot (t' = t + kW), only the latest valid one lands
        write = valid & (jnp.arange(T)[None, :] + W >= clens[:, None])
        slot = jnp.where(write, positions % W, W)            # OOB -> drop
        b_ix = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T)).reshape(-1)
        kc = kc.at[b_ix, :, slot.reshape(-1), :].set(
            k.reshape(B * T, cfg.n_kv_heads, cfg.hd).astype(kc.dtype),
            mode="drop")
        vc = vc.at[b_ix, :, slot.reshape(-1), :].set(
            v.reshape(B * T, cfg.n_kv_heads, cfg.hd).astype(vc.dtype),
            mode="drop")
        new_entry = {"k": kc, "v": vc}

    out = attend(q, kg.astype(q.dtype), vg.astype(q.dtype), positions, kv_pos,
                 kind=kind, window=cfg.attn.window,
                 softcap=softcap, impl=impl, block_q=block_q,
                 block_kv=block_kv, sharder=sharder)
    return out, new_entry


# ---------------------------------------------------------------------------
# Attention block (projections + cache plumbing)
# ---------------------------------------------------------------------------

def init_attn(cfg: ModelConfig, key: Array, dtype) -> Dict[str, Array]:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, cfg.q_dim), dtype),
        "wk": layers.dense_init(ks[1], (d, cfg.kv_dim), dtype),
        "wv": layers.dense_init(ks[2], (d, cfg.kv_dim), dtype),
        "wo": layers.dense_init(ks[3], (cfg.q_dim, d), dtype, fan_in=cfg.q_dim),
    }
    if cfg.attn.qk_norm:
        p["q_norm"] = jnp.ones((cfg.hd,), dtype)
        p["k_norm"] = jnp.ones((cfg.hd,), dtype)
    return p


def _qk_norm(x: Array, scale: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def apply_attention_block(
    cfg: ModelConfig, p: Dict[str, Array], x: Array, positions: Array, *,
    kind: str, mode: str = "train", cache: Optional[Dict[str, Array]] = None,
    prefill_cache_len: Optional[int] = None,
    lora: Optional[Dict] = None, adapter_idx: Optional[Array] = None,
    noise: Optional[NoiseConfig] = None, rng: Optional[Array] = None,
    impl: str = "auto", block_q: int = 2048, block_kv: int = 512,
    sharder=None, paged: Optional[Dict[str, Array]] = None,
    chunk_lens: Optional[Array] = None,
) -> Tuple[Array, Optional[Dict[str, Array]]]:
    """MHA-1..MHA-4 for one layer. Returns (out, new_cache).

    mode: "train" (no cache), "prefill" (self-attend + emit cache of
    ``prefill_cache_len``), "decode" (append to cache, attend over it).
    Cache layout: k/v (B, Hkv, S_cache, D) — head_dim is the TP-sharded dim
    so the seq append lands on an unsharded axis.

    ``paged`` switches decode to the paged/chunked path: the cache entry is
    a shared page pool (full attn) or a per-slot ring without a "len" leaf
    (sliding), request lengths live in ``paged["lens"]``, and the incoming
    (B, T) chunk may be ragged per row (``paged["chunk_lens"]``).

    ``chunk_lens`` (B,) makes PREFILL ragged: row ``b`` holds
    ``chunk_lens[b]`` real tokens followed by padding. Pad tokens are
    invisible as keys, the emitted cache ``len`` is the true per-row
    length, and the sliding ring is built from each row's last real
    tokens — so one bucketed prefill compile serves every prompt length
    (pad-row outputs are finite garbage the caller discards)."""
    B, T, d = x.shape
    scale = lora_scale(cfg)

    def proj(name, target):
        y = hetero.static_matmul(x, p[name], noise=noise, rng=rng)
        if lora is not None and target in lora:
            y = y + lora_delta(x, lora[target], scale, adapter_idx)
        return y

    q = proj("wq", "wq").reshape(B, T, cfg.n_heads, cfg.hd)
    k = proj("wk", "wk").reshape(B, T, cfg.n_kv_heads, cfg.hd)
    v = proj("wv", "wv").reshape(B, T, cfg.n_kv_heads, cfg.hd)

    if cfg.attn.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
        k = _qk_norm(k, p["k_norm"], cfg.norm_eps)

    if cfg.attn.rope_theta is not None:   # None: position comes from elsewhere
        sin, cos = layers.rope_sincos(positions, cfg.hd, cfg.attn.rope_theta)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)

    new_cache = None
    if mode == "decode" and paged is not None:
        out, new_cache = _paged_attend(
            cfg, q, k, v, positions, cache, paged, kind=kind,
            softcap=cfg.attn.logit_softcap, impl=impl, block_q=block_q,
            block_kv=block_kv, sharder=sharder)
    elif mode == "decode":
        assert cache is not None
        # ---- decode: append to (B, Hkv, S, D) cache ----
        # "len" is per-row (B,): slots in a continuous-batching arena sit at
        # different positions (scalar still accepted for uniform decode).
        cur = cache["len"]
        if cur.ndim == 0:
            cur = jnp.broadcast_to(cur, (B,))
        kc, vc = cache["k"], cache["v"]
        S_cache = kc.shape[2]
        k_t = k.transpose(0, 2, 1, 3)  # (B, Hkv, T, D)
        v_t = v.transpose(0, 2, 1, 3)

        def row_update(c, u, start):
            return jax.lax.dynamic_update_slice(c, u.astype(c.dtype),
                                                (0, start, 0))

        i = jnp.arange(S_cache)
        if kind == "sliding":
            W = S_cache
            kc = jax.vmap(row_update)(kc, k_t, cur % W)
            vc = jax.vmap(row_update)(vc, v_t, cur % W)
            # slot i holds the latest position == i (mod W) strictly < cur+T
            last = cur[:, None] + T - 1
            kv_pos = last - ((last - i[None, :]) % W)
        else:
            kc = jax.vmap(row_update)(kc, k_t, cur)
            vc = jax.vmap(row_update)(vc, v_t, cur)
            kv_pos = jnp.where(i[None, :] < cur[:, None] + T, i[None, :], -1)
        new_cache = {"k": kc, "v": vc, "len": cur + T}
        if sharder is not None:
            kc = sharder(kc, "kv_cache")
            vc = sharder(vc, "kv_cache")
            q = sharder(q, "decode_q")   # align q with the hd-sharded cache
        k_at, v_at = kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3)
        out = attend(q, k_at.astype(q.dtype), v_at.astype(q.dtype), positions,
                     kv_pos, kind=kind, window=cfg.attn.window,
                     softcap=cfg.attn.logit_softcap, impl=impl,
                     block_q=block_q, block_kv=block_kv, sharder=sharder)
    else:
        # ---- train / prefill: self-attention ----
        if sharder is not None:   # gather KV over the model axis (SP)
            k = sharder(k, "kv_gathered")
            v = sharder(v, "kv_gathered")
        kv_pos = positions
        if mode == "prefill" and chunk_lens is not None:
            # ragged bucketed prefill: the padded tail is invisible as keys
            kv_pos = jnp.where(jnp.arange(T)[None, :] < chunk_lens[:, None],
                               kv_pos, -1)
        if sharder is not None:
            kv_pos = sharder(kv_pos, "pos_gathered")
        out = attend(q, k, v, positions, kv_pos, kind=kind,
                     window=cfg.attn.window, softcap=cfg.attn.logit_softcap,
                     impl=impl, block_q=block_q, block_kv=block_kv,
                     sharder=sharder)
        if mode == "prefill":
            S_cache = prefill_cache_len if prefill_cache_len is not None else T
            k_t = k.transpose(0, 2, 1, 3)  # (B, Hkv, T_full, D)
            v_t = v.transpose(0, 2, 1, 3)
            T_full = k_t.shape[2]
            if kind == "sliding":
                W = min(cfg.attn.window, S_cache)
                i = jnp.arange(W)
                # slot i holds the latest position == i (mod W) below the
                # row's real length (T_full when the chunk is not ragged)
                last = (jnp.full((B, 1), T_full, jnp.int32)
                        if chunk_lens is None else chunk_lens[:, None]) - 1
                slot_src = last - ((last - i[None, :]) % W)   # (B, W)
                src = jnp.clip(slot_src, 0, max(T_full - 1, 0))
                kc = jnp.take_along_axis(k_t, src[:, None, :, None], axis=2)
                vc = jnp.take_along_axis(v_t, src[:, None, :, None], axis=2)
            else:
                pad = S_cache - T_full
                kc = jnp.pad(k_t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                vc = jnp.pad(v_t, ((0, 0), (0, 0), (0, pad), (0, 0)))
            lens_out = (jnp.full((B,), T_full, jnp.int32)
                        if chunk_lens is None
                        else chunk_lens.astype(jnp.int32))
            new_cache = {"k": kc.astype(q.dtype), "v": vc.astype(q.dtype),
                         "len": lens_out}
            if sharder is not None:
                new_cache["k"] = sharder(new_cache["k"], "kv_cache")
                new_cache["v"] = sharder(new_cache["v"], "kv_cache")

    out = out.reshape(B, T, cfg.q_dim)
    y = hetero.static_matmul(out, p["wo"], noise=noise, rng=rng)
    if lora is not None and "wo" in lora:
        y = y + lora_delta(out, lora["wo"], scale, adapter_idx)
    return y, new_cache

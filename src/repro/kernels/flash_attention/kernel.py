"""Pallas TPU kernels: fused score+softmax+V attention and its gradients
(Atleus's DYNAMIC engine / systolic-array computation, SS IV.A ref [39]).

Output-stationary dataflow: each program keeps its accumulators (output
or dQ for a q block, dK/dV for a kv block) and the running statistics in
VMEM scratch while the other operand's blocks stream from HBM — the
direct analogue of the paper's OS systolic mapping for dynamic-operand
matmuls. No (T, S) array ever leaves VMEM.

Layout. A program serves one KV head and the G = Hq/Hkv query heads that
share it: q/o/dO are (B*Hkv, G, T, D), k/v (B*Hkv, S, D). The G heads' rows
are folded into one (G*bq) axis, so a K/V block is read once for all of
them and dK/dV accumulate over them in place. Scores are kept transposed,
(bk, G*bq): per-query statistics (max, sum, log-sum-exp, D_i) are then
lane-dense rows, reduced over sublanes, and stored as (B*Hkv, G, 1, T).

Masks come from explicit positions (causal, invalid slot = -1, optional
sliding window in the forward). ``block_visibility`` classifies each
(q block, kv block) pair from the positions alone: a pair the mask hides
entirely is skipped under ``pl.when`` and its K/V (or Q) block index is
clamped into the range of blocks that are used, so no DMA is issued for
it; a pair the mask does not touch runs without a mask.

Precision: operands load in their stored dtype and are cast to
``dot_dtype`` right before each dot, with float32 accumulation; every
statistic and accumulator is float32. By default (``dot_dtype=None``) the
cast is to bfloat16 when compiled for the TPU — what XLA's default
precision does with float32 operands there — and the interpreter keeps
the stored dtype, as XLA's dots do on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SKIP, PARTIAL, FULL = 0, 1, 2
_FAR = 2 ** 30          # beyond any position, for blocks without a valid key

_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _dot_dtype(dot_dtype, interpret):
    """The dtype each dot's operands are cast to (None: left as stored)."""
    if dot_dtype is not None:
        return dot_dtype
    return None if interpret else jnp.bfloat16


def _dot(a, b, contract, dot_dtype):
    if dot_dtype is not None:
        a, b = a.astype(dot_dtype), b.astype(dot_dtype)
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def block_visibility(q_pos, kv_pos, block_q, block_kv, window=None):
    """(B, nq, nk) int32: SKIP where the mask hides every key of the kv
    block from every query of the q block, FULL where it hides none,
    PARTIAL otherwise. Decided from each block's position bounds."""
    B, T = q_pos.shape
    S = kv_pos.shape[1]
    qb = q_pos.reshape(B, T // block_q, block_q)
    qmin = qb.min(-1)[:, :, None]
    qmax = qb.max(-1)[:, :, None]
    kb = kv_pos.reshape(B, S // block_kv, block_kv)
    ok = kb >= 0
    kmin = jnp.where(ok, kb, _FAR).min(-1)[:, None, :]
    kmax = jnp.where(ok, kb, -_FAR).max(-1)[:, None, :]
    skip = kmin > qmax
    full = ok.all(-1)[:, None, :] & (kmax <= qmin)
    if window is not None:
        skip |= (qmin - kmax) >= window
        full &= (qmax - kmin) < window
    return jnp.where(skip, SKIP, jnp.where(full, FULL, PARTIAL)).astype(
        jnp.int32)


def _used_span(vis, axis):
    """First and last block index along ``axis`` that is not skipped,
    flattened over the other two axes (0, 0 where every block is)."""
    n = vis.shape[axis]
    idx = jnp.arange(n).reshape([-1 if a == axis else 1 for a in range(3)])
    used = vis != SKIP
    lo = jnp.min(jnp.where(used, idx, n), axis)
    hi = jnp.max(jnp.where(used, idx, -1), axis)
    lo = jnp.where(lo == n, 0, lo)
    hi = jnp.maximum(hi, 0)
    return lo.reshape(-1).astype(jnp.int32), hi.reshape(-1).astype(jnp.int32)


def _visible(qp, kp, groups, window=None):
    """(bk, groups*bq) mask from a (1, bq) query row and a (bk, 1) key
    column; the row repeats once per folded query head."""
    if groups > 1:
        qp = jnp.concatenate([qp] * groups, axis=1)
    m = (kp <= qp) & (kp >= 0)
    if window is not None:
        m &= (qp - kp) < window
    return m


def _row(ref, groups):
    """(1, groups*bq) row from a (1, groups, 1, bq) block."""
    rows = [ref[0, g] for g in range(groups)]
    return rows[0] if groups == 1 else jnp.concatenate(rows, axis=1)


def _store_rows_transposed(ref, xT, groups, block_q):
    """Write a (D, groups*bq) block as ``groups`` (bq, D) blocks."""
    for g in range(groups):
        ref[0, g] = xT[:, g * block_q:(g + 1) * block_q].T.astype(ref.dtype)


def _run_by_visibility(state, body):
    pl.when(state == FULL)(functools.partial(body, False))
    pl.when(state == PARTIAL)(functools.partial(body, True))


def _clamped(j, lo_ref, hi_ref, r):
    return jnp.minimum(jnp.maximum(j, lo_ref[r]), hi_ref[r])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(vis_ref, lo_ref, hi_ref, qp_ref, kp_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_sc, l_sc, acc_sc, *, heads_kv, scale,
                window, softcap, dot_dtype):
    p, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    _, G, bq, D = q_ref.shape

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(masked):
        q = q_ref[0].reshape(G * bq, D) * scale
        s = _dot(k_ref[0], q, _NT, dot_dtype)             # (bk, G*bq)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            mask = _visible(qp_ref[0], kp_ref[0], G, window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        pt = jnp.exp(s - m_new)
        if masked:
            pt = jnp.where(mask, pt, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(pt, axis=0, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr + _dot(v_ref[0], pt, _TN, dot_dtype)

    b = p // heads_kv
    _run_by_visibility(vis_ref[(b * nq + i) * nk + j], body)

    @pl.when(j == nk - 1)
    def _done():
        l = jnp.maximum(l_sc[...], 1e-30)
        _store_rows_transposed(o_ref, acc_sc[...] / l, G, bq)
        lse = m_sc[...] + jnp.log(l)
        for g in range(G):
            lse_ref[0, g] = lse[:, g * bq:(g + 1) * bq]


@functools.partial(jax.jit, static_argnames=("window", "softcap", "block_q",
                                             "block_kv", "interpret",
                                             "dot_dtype"))
def flash_fwd(q, k, v, q_pos, kv_pos, *, window=None, softcap=None,
              block_q=512, block_kv=512, interpret=False, dot_dtype=None):
    """q (B*Hkv, G, T, D); k/v (B*Hkv, S, D); q_pos (B, T); kv_pos (B, S).
    Returns the output (B*Hkv, G, T, D) in q's dtype and the float32
    log-sum-exp of each query's scores (B*Hkv, G, 1, T)."""
    BHkv, G, T, D = q.shape
    S = k.shape[1]
    B = q_pos.shape[0]
    assert T % block_q == 0 and S % block_kv == 0
    nq, nk = T // block_q, S // block_kv
    heads_kv = BHkv // B
    vis = block_visibility(q_pos, kv_pos, block_q, block_kv, window)
    lo, hi = _used_span(vis, 2)
    vis = vis.reshape(-1)

    def kv_block(p, i, j, vis, lo, hi):
        return _clamped(j, lo, hi, (p // heads_kv) * nq + i)

    kern = functools.partial(
        _fwd_kernel, heads_kv=heads_kv, scale=D ** -0.5, window=window,
        softcap=softcap, dot_dtype=_dot_dtype(dot_dtype, interpret))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BHkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q),
                         lambda p, i, j, *_: (p // heads_kv, 0, i)),
            pl.BlockSpec((1, block_kv, 1),
                         lambda p, i, j, *s: (p // heads_kv,
                                              kv_block(p, i, j, *s), 0)),
            pl.BlockSpec((1, G, block_q, D), lambda p, i, j, *_: (p, 0, i, 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda p, i, j, *s: (p, kv_block(p, i, j, *s), 0)),
            pl.BlockSpec((1, block_kv, D),
                         lambda p, i, j, *s: (p, kv_block(p, i, j, *s), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, G, block_q, D), lambda p, i, j, *_: (p, 0, i, 0)),
            pl.BlockSpec((1, G, 1, block_q), lambda p, i, j, *_: (p, 0, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, G * block_q), jnp.float32),
            pltpu.VMEM((1, G * block_q), jnp.float32),
            pltpu.VMEM((D, G * block_q), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((BHkv, G, T, D), q.dtype),
                   jax.ShapeDtypeStruct((BHkv, G, 1, T), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(vis, lo, hi, q_pos[:, None, :], kv_pos[:, :, None], q, k, v)


# ---------------------------------------------------------------------------
# backward: p is recomputed from q, k and the log-sum-exp; D_i = rowsum(dO*O)
# ---------------------------------------------------------------------------

def _probs_and_dscores(qp_ref, kp_ref, q_ref, do_ref, lse_ref, di_ref, k, v,
                       masked, scale, dot_dtype):
    """p^T and dS^T, each (bk, G*bq), with the scaled q and dO rows."""
    _, G, bq, D = q_ref.shape
    q = q_ref[0].reshape(G * bq, D) * scale
    do = do_ref[0].reshape(G * bq, D)
    pt = jnp.exp(_dot(k, q, _NT, dot_dtype) - _row(lse_ref, G))
    if masked:
        pt = jnp.where(_visible(qp_ref[0], kp_ref[0], G), pt, 0.0)
    dst = pt * (_dot(v, do, _NT, dot_dtype) - _row(di_ref, G))
    return pt, dst, q, do


def _dkv_kernel(vis_ref, lo_ref, hi_ref, qp_ref, kp_ref, q_ref, do_ref,
                lse_ref, di_ref, k_ref, v_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                *, heads_kv, scale, dot_dtype):
    p, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def body(masked):
        pt, dst, q, do = _probs_and_dscores(
            qp_ref, kp_ref, q_ref, do_ref, lse_ref, di_ref, k_ref[0],
            v_ref[0], masked, scale, dot_dtype)
        dv_sc[...] += _dot(pt, do, _NN, dot_dtype)
        dk_sc[...] += _dot(dst, q, _NN, dot_dtype)

    b = p // heads_kv
    _run_by_visibility(vis_ref[(b * nq + i) * nk + j], body)

    @pl.when(i == nq - 1)
    def _done():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(vis_ref, lo_ref, hi_ref, qp_ref, kp_ref, q_ref, do_ref,
               lse_ref, di_ref, k_ref, v_ref, dq_ref, dq_sc, *, heads_kv,
               scale, dot_dtype):
    p, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    _, G, bq, _ = q_ref.shape

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def body(masked):
        k = k_ref[0]
        _, dst, _, _ = _probs_and_dscores(
            qp_ref, kp_ref, q_ref, do_ref, lse_ref, di_ref, k, v_ref[0],
            masked, scale, dot_dtype)
        dq_sc[...] += _dot(k, dst, _TN, dot_dtype)        # (D, G*bq)

    b = p // heads_kv
    _run_by_visibility(vis_ref[(b * nq + i) * nk + j], body)

    @pl.when(j == nk - 1)
    def _done():
        _store_rows_transposed(dq_ref, dq_sc[...] * scale, G, bq)


@functools.partial(jax.jit, static_argnames=("dkv_blocks", "dq_blocks",
                                             "interpret", "dot_dtype"))
def flash_bwd(q, k, v, q_pos, kv_pos, do, lse, di, *, dkv_blocks=(512, 512),
              dq_blocks=(512, 512), interpret=False, dot_dtype=None):
    """Causal attention gradients. q/do (B*Hkv, G, T, D); k/v (B*Hkv, S, D);
    lse and di = rowsum(do*o), float32 (B*Hkv, G, 1, T). Blocks are
    (q, kv) pairs. Returns float32 dq (B*Hkv, G, T, D), dk and dv
    (B*Hkv, S, D)."""
    BHkv, G, T, D = q.shape
    S = k.shape[1]
    B = q_pos.shape[0]
    heads_kv = BHkv // B
    qp, kp = q_pos[:, None, :], kv_pos[:, :, None]
    dot_dtype = _dot_dtype(dot_dtype, interpret)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    def specs(bq, bk, q_block, kv_block):
        """Operand specs, given the q and kv block index of each program."""
        row = lambda p, *ids: (p // heads_kv, 0, q_block(p, *ids))
        col = lambda p, *ids: (p // heads_kv, kv_block(p, *ids), 0)
        qo = lambda p, *ids: (p, 0, q_block(p, *ids), 0)
        stat = lambda p, *ids: (p, 0, 0, q_block(p, *ids))
        kv = lambda p, *ids: (p, kv_block(p, *ids), 0)
        return [pl.BlockSpec((1, 1, bq), row),
                pl.BlockSpec((1, bk, 1), col),
                pl.BlockSpec((1, G, bq, D), qo),
                pl.BlockSpec((1, G, bq, D), qo),
                pl.BlockSpec((1, G, 1, bq), stat),
                pl.BlockSpec((1, G, 1, bq), stat),
                pl.BlockSpec((1, bk, D), kv),
                pl.BlockSpec((1, bk, D), kv)]

    # dK, dV: a program per kv block, streaming the q blocks that see it
    bq, bk = dkv_blocks
    assert T % bq == 0 and S % bk == 0
    nq, nk = T // bq, S // bk
    vis = block_visibility(q_pos, kv_pos, bq, bk)
    lo, hi = _used_span(vis, 1)
    q_of = lambda p, j, i, vis, lo, hi: _clamped(i, lo, hi,
                                                 (p // heads_kv) * nk + j)
    kv_of = lambda p, j, i, *_: j
    dkv_spec = pl.BlockSpec((1, bk, D), lambda p, j, i, *_: (p, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, heads_kv=heads_kv, scale=D ** -0.5,
                          dot_dtype=dot_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BHkv, nk, nq),
            in_specs=specs(bq, bk, q_of, kv_of),
            out_specs=[dkv_spec, dkv_spec],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BHkv, S, D), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(vis.reshape(-1), lo, hi, qp, kp, q, do, lse, di, k, v)

    # dQ: a program per q block, streaming the kv blocks it sees
    bq, bk = dq_blocks
    assert T % bq == 0 and S % bk == 0
    nq, nk = T // bq, S // bk
    vis = block_visibility(q_pos, kv_pos, bq, bk)
    lo, hi = _used_span(vis, 2)
    q_of = lambda p, i, j, *_: i
    kv_of = lambda p, i, j, vis, lo, hi: _clamped(j, lo, hi,
                                                  (p // heads_kv) * nq + i)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, heads_kv=heads_kv, scale=D ** -0.5,
                          dot_dtype=dot_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BHkv, nq, nk),
            in_specs=specs(bq, bk, q_of, kv_of),
            out_specs=pl.BlockSpec((1, G, bq, D),
                                   lambda p, i, j, *_: (p, 0, i, 0)),
            scratch_shapes=[pltpu.VMEM((D, G * bq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BHkv, G, T, D), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(vis.reshape(-1), lo, hi, qp, kp, q, do, lse, di, k, v)
    return dq, dk, dv

"""Pallas TPU kernel: RWKV6 wkv recurrence with VMEM-resident state.

The (N, N) per-head state never leaves VMEM while T steps stream past —
the output-stationary dataflow the paper assigns to dynamic recurrences
(DESIGN.md SS5): under XLA the sequential scan writes the state to HBM
every step (the dominant term of rwkv6-7b's memory roofline); here it is
scratch that persists across the time-block grid dimension.

Grid: (B*H, T/bt). Inside a block, a fori_loop walks bt steps entirely in
registers/VMEM:   y_t = r_t (S + u ⊙ k_t v_t^T);  S <- diag(w_t) S + k_t v_t^T
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_ref, *, n_t, block_t):
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _init():
        s_ref[...] = s0_ref[0]

    N = s_ref.shape[0]
    # row -> column through the identity (a lane reduction): every vector
    # stays 2D, so the TPU lowering needs no relayout of 1D values
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
           ).astype(jnp.float32)

    def col(row):                                   # (1, N) -> (N, 1)
        return jnp.sum(eye * row, axis=1, keepdims=True)

    u = u_ref[0]                                    # (1, N)

    def step(i, _):
        rt = r_ref[0, pl.ds(i, 1), :]               # (1, N)
        kt = k_ref[0, pl.ds(i, 1), :]
        vt = v_ref[0, pl.ds(i, 1), :]
        wt = w_ref[0, pl.ds(i, 1), :]
        s = s_ref[...]                              # (N, N)
        kv = col(kt) * vt                           # k_t v_t^T
        # r_t (S + u ⊙ k_t v_t^T) = r_t S + (Σ r u k) v_t
        y = (jnp.sum(col(rt) * s, axis=0, keepdims=True)
             + jnp.sum(rt * u * kt, axis=1, keepdims=True) * vt)
        y_ref[0, pl.ds(i, 1), :] = y.astype(y_ref.dtype)
        s_ref[...] = col(wt) * s + kv
        return ()

    jax.lax.fori_loop(0, block_t, step, ())

    @pl.when(t_blk == n_t - 1)
    def _done():
        sout_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_wkv_kernel(r, k, v, w, u, s0, *, block_t=64, interpret=False):
    """r/k/v/w (BH, T, N) f32; u (BH, N); s0 (BH, N, N).
    Returns y (BH, T, N), s_final (BH, N, N)."""
    BH, T, N = r.shape
    assert T % block_t == 0
    n_t = T // block_t
    grid = (BH, n_t)
    kern = functools.partial(_wkv_kernel, n_t=n_t, block_t=block_t)
    seq_spec = pl.BlockSpec((1, block_t, N), lambda b, t: (b, t, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            # (BH, 1, N): a full-extent (1, N) tile per head (the TPU
            # lowering refuses a (1, N) block of a (BH, N) array)
            pl.BlockSpec((1, 1, N), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, N, N), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, N, N), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u[:, None, :], s0)

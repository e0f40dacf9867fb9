"""Pallas TPU kernels: Mamba-1's selective scan and its gradients, with the
state resident in VMEM.

    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t        y_t = sum_n C_t[n] h_t[n]

The decay differs per (channel, state) pair, so the scan has no matmul
form; it is a sequential recurrence over time, as in the upstream CUDA
``selective_scan``. A program serves one batch row and one block of
channels, and walks the time blocks in order (the grid's last axis) while
its state stays in VMEM scratch. HBM sees only dt, x, B, C and y (and, for
the backward, dy and the gradients), never a (T, D, N) tensor.

Layout. The state is (N, Db): d_state on sublanes, channels on lanes, so
N = 16 by Db = 512 is 8 vregs. dt, x and y are (B, T, D) as the model
holds them; a time step reads one (1, Db) row of each. B and C come
transposed, (B, N, T), so a time block is one (N, bt) tile with time on
lanes; step j takes its column by a lane mask and a lane sum, which needs
no dynamic lane slice. The backward's dB and dC partials are written the
same way, one (N, bt) tile per block and channel block, summed over the
channel blocks outside.

Arithmetic: float32 throughout, on the VPU and EUP only. No product goes
through the MXU, so the C read-out is an exact float32 sum over N.

Backward: the forward saves the state at each time block's start. The
backward walks the blocks in reverse (through the index maps), recomputes
the block's states from its saved start into a (bt, N, Db) VMEM buffer,
then runs backward in time with g_{t-1} = a_t g_t and g_t += C_t dy_t; it
never divides by a decay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# time steps per loop iteration, so the scheduler overlaps one step's decay
# and increment with the previous step's state update
UNROLL = 8


def _column(tile, lane, j):
    """Column ``j`` of an (N, bt) tile as (N, 1)."""
    return jnp.sum(jnp.where(lane == j, tile, 0.0), axis=1, keepdims=True)


def _loop(n, body, carry):
    """``body(j, carry)`` for j in 0..n-1, ``UNROLL`` steps per iteration
    (Mosaic unrolls a ``fori_loop`` only wholly or not at all)."""
    def group(i, carry):
        for k in range(UNROLL):
            carry = body(i * UNROLL + k, carry)
        return carry
    return jax.lax.fori_loop(0, n // UNROLL, group, carry)


def _fwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
                y_ref, hfin_ref, hs_ref, h_sc, *, block_t):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_sc[...] = h0_ref[0]

    h = h_sc[...]
    hs_ref[0, 0] = h
    A = a_ref[...]                                   # (N, Db)
    bT, cT = b_ref[0], c_ref[0]                      # (N, bt)
    lane = jax.lax.broadcasted_iota(jnp.int32, bT.shape, 1)

    def step(j, h):
        dt = dt_ref[0, pl.ds(j, 1), :]               # (1, Db)
        x = x_ref[0, pl.ds(j, 1), :]
        h = (jnp.exp(dt * A) * h
             + (dt * x) * _column(bT, lane, j))
        y_ref[0, pl.ds(j, 1), :] = jnp.sum(_column(cT, lane, j) * h, axis=0,
                                           keepdims=True)
        return h

    h = _loop(block_t, step, h)
    h_sc[...] = h

    @pl.when(t == pl.num_programs(2) - 1)
    def _done():
        hfin_ref[0] = h


def _bwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, hs_ref, dy_ref, dhfin_ref,
                ddt_ref, dx_ref, db_ref, dc_ref, da_ref, dh0_ref,
                g_sc, da_sc, h_buf, *, block_t):
    s = pl.program_id(2)                             # block n_t - 1 - s

    @pl.when(s == 0)
    def _init():
        g_sc[...] = dhfin_ref[0]
        da_sc[...] = jnp.zeros_like(da_sc)

    A = a_ref[...]
    bT, cT = b_ref[0], c_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, bT.shape, 1)

    # the block's states, forward from its saved start; dC needs h_t
    def states(j, carry):
        h, dc = carry
        dt = dt_ref[0, pl.ds(j, 1), :]
        x = x_ref[0, pl.ds(j, 1), :]
        dy = dy_ref[0, pl.ds(j, 1), :]
        h_buf[j] = h                                 # h_{j-1}
        h = (jnp.exp(dt * A) * h
             + (dt * x) * _column(bT, lane, j))
        dc = jnp.where(lane == j, jnp.sum(dy * h, axis=1, keepdims=True), dc)
        return h, dc

    _, dc = _loop(block_t, states, (hs_ref[0, 0], jnp.zeros_like(cT)))
    dc_ref[0, 0] = dc

    def back(i, carry):
        g, dA, db = carry
        j = block_t - 1 - i
        dt = dt_ref[0, pl.ds(j, 1), :]
        x = x_ref[0, pl.ds(j, 1), :]
        dy = dy_ref[0, pl.ds(j, 1), :]
        bj = _column(bT, lane, j)
        g = g + _column(cT, lane, j) * dy            # dL/dh_j
        a = jnp.exp(dt * A)
        dz = g * h_buf[j] * a                        # dL/d(dt_j A)
        dbx = jnp.sum(g * bj, axis=0, keepdims=True)  # dL/d(dt_j x_j)
        ddt_ref[0, pl.ds(j, 1), :] = (jnp.sum(dz * A, axis=0, keepdims=True)
                                      + dbx * x)
        dx_ref[0, pl.ds(j, 1), :] = dbx * dt
        db = jnp.where(lane == j,
                       jnp.sum(g * (dt * x), axis=1, keepdims=True), db)
        return a * g, dA + dt * dz, db

    g, dA, db = _loop(block_t, back,
                      (g_sc[...], da_sc[...], jnp.zeros_like(bT)))
    g_sc[...] = g
    da_sc[...] = dA
    db_ref[0, 0] = db

    @pl.when(s == pl.num_programs(2) - 1)
    def _done():
        da_ref[0] = dA
        dh0_ref[0] = g


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def selective_scan_fwd(dt, x, bT, cT, aT, h0T, *, block_t, block_d,
                       interpret=False):
    """dt, x (B, T, D); bT, cT (B, N, T); aT (N, D); h0T (B, N, D); all
    float32, T a multiple of ``block_t`` and D of ``block_d``. Returns y
    (B, T, D), the final state (B, N, D) and the state at each time
    block's start (B, T/block_t, N, D)."""
    B, T, D = dt.shape
    N = aT.shape[0]
    n_t, n_d = T // block_t, D // block_d
    seq = pl.BlockSpec((1, block_t, block_d), lambda b, d, t: (b, t, d))
    cols = pl.BlockSpec((1, N, block_t), lambda b, d, t: (b, 0, t))
    state = pl.BlockSpec((1, N, block_d), lambda b, d, t: (b, 0, d))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_t=block_t),
        grid=(B, n_d, n_t),
        in_specs=[seq, seq, cols, cols,
                  pl.BlockSpec((N, block_d), lambda b, d, t: (0, d)), state],
        out_specs=[seq, state,
                   pl.BlockSpec((1, 1, N, block_d),
                                lambda b, d, t: (b, t, 0, d))],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, n_t, N, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="selective_scan_fwd",
    )(dt, x, bT, cT, aT, h0T)


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def selective_scan_bwd(dt, x, bT, cT, aT, hs, dy, dhT, *, block_t, block_d,
                       interpret=False):
    """The forward's inputs, its block-start states ``hs`` and the
    cotangents dy (B, T, D), dhT (B, N, D) of y and the final state.
    Returns ddt, dx (B, T, D); dB, dC partials (B, D/block_d, N, T), one
    per channel block; dA partials (B, N, D), one per row; dh0 (B, N, D)."""
    B, T, D = dt.shape
    N = aT.shape[0]
    n_t, n_d = T // block_t, D // block_d

    def rev(t):
        return n_t - 1 - t

    seq = pl.BlockSpec((1, block_t, block_d), lambda b, d, t: (b, rev(t), d))
    cols = pl.BlockSpec((1, N, block_t), lambda b, d, t: (b, 0, rev(t)))
    part = pl.BlockSpec((1, 1, N, block_t), lambda b, d, t: (b, d, 0, rev(t)))
    state = pl.BlockSpec((1, N, block_d), lambda b, d, t: (b, 0, d))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_t=block_t),
        grid=(B, n_d, n_t),
        in_specs=[seq, seq, cols, cols,
                  pl.BlockSpec((N, block_d), lambda b, d, t: (0, d)),
                  pl.BlockSpec((1, 1, N, block_d),
                               lambda b, d, t: (b, rev(t), 0, d)),
                  seq, state],
        out_specs=[seq, seq, part, part, state, state],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, n_d, N, T), jnp.float32),
                   jax.ShapeDtypeStruct((B, n_d, N, T), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32),
                        pltpu.VMEM((N, block_d), jnp.float32),
                        pltpu.VMEM((block_t, N, block_d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="selective_scan_bwd",
    )(dt, x, bT, cT, aT, hs, dy, dhT)

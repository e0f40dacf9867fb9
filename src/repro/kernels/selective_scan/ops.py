"""Public wrappers: the model's layouts in and out, padding, block sizes.

dt, x (B, T, D); Bc, Cc (B, T, N); A (D, N); h0 (B, D, N), all float32.
T is padded to a whole number of time blocks with dt = 0, an identity step
(exp(0) = 1, no increment), so the final state is exact; D is padded to a
whole number of channel blocks with dt = A = 0, channels that stay at 0.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.selective_scan.kernel import (selective_scan_bwd,
                                                 selective_scan_fwd)

# A time block is one lane tile of the transposed B and C; the backward
# keeps its (BLOCK_T, N, block_d) float32 states in VMEM: 4 MiB at N = 16
# and block_d = 512, inside the default scoped VMEM limit.
BLOCK_T = 128
_BLOCK_D = (512, 256, 128)


def applies(T: int) -> bool:
    """Whether the kernels pay off for a scan over T steps: at least one
    time block (decode steps and short chunks stay in XLA)."""
    return T >= BLOCK_T


def _blocks(T, D):
    """(padded T, padded D, channel block)."""
    Tp = -(-T // BLOCK_T) * BLOCK_T
    Dp = -(-D // 128) * 128
    return Tp, Dp, next(b for b in _BLOCK_D if Dp % b == 0)


def block_states_shape(shape, N):
    """Shape of the block-start states ``scan_fwd`` returns for dt of
    ``shape`` (B, T, D)."""
    B, T, D = shape
    Tp, Dp, _ = _blocks(T, D)
    return B, Tp // BLOCK_T, N, Dp


def _pad(a, axis, size):
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, pad) if size != a.shape[axis] else a


def _inputs(dt, Bc, Cc, x, A, Tp, Dp):
    f32 = jnp.float32
    dt, x = (_pad(_pad(a.astype(f32), 1, Tp), 2, Dp) for a in (dt, x))
    bT, cT = (_pad(a.astype(f32), 1, Tp).transpose(0, 2, 1) for a in (Bc, Cc))
    aT = _pad(A.astype(f32), 0, Dp).T
    return dt, x, bT, cT, aT


def scan_fwd(dt, Bc, Cc, x, A, h0, *, interpret=False):
    """y (B, T, D), the final state (B, D, N) and the residual the
    backward needs: the state at each time block's start, (B, Tp/BLOCK_T,
    N, Dp)."""
    B, T, D = dt.shape
    Tp, Dp, bd = _blocks(T, D)
    h0T = _pad(h0.astype(jnp.float32), 1, Dp).transpose(0, 2, 1)
    y, hT, hs = selective_scan_fwd(*_inputs(dt, Bc, Cc, x, A, Tp, Dp), h0T,
                                   block_t=BLOCK_T, block_d=bd,
                                   interpret=interpret)
    return y[:, :T, :D], hT[:, :, :D].transpose(0, 2, 1), hs


def scan_bwd(dt, Bc, Cc, x, A, hs, dy, dh, *, interpret=False):
    """Gradients (ddt, dBc, dCc, dx, dA, dh0) in the forward's layouts, from
    its inputs, the block-start states ``scan_fwd`` returned and the
    cotangents of y and the final state."""
    B, T, D = dt.shape
    Tp, Dp, bd = _blocks(T, D)
    dyp = _pad(_pad(dy.astype(jnp.float32), 1, Tp), 2, Dp)
    dhT = _pad(dh.astype(jnp.float32), 1, Dp).transpose(0, 2, 1)
    ddt, dx, db, dc, da, dh0 = selective_scan_bwd(
        *_inputs(dt, Bc, Cc, x, A, Tp, Dp), hs, dyp, dhT, block_t=BLOCK_T,
        block_d=bd, interpret=interpret)

    def rows(p):                       # (B, n_d, N, Tp) -> (B, T, N)
        return p.sum(1).transpose(0, 2, 1)[:, :T]

    return (ddt[:, :T, :D], rows(db), rows(dc), dx[:, :T, :D],
            da.sum(0).T[:D], dh0[:, :, :D].transpose(0, 2, 1))

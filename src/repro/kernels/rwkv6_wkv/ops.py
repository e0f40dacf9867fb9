"""Public wrapper: (B, T, H, N) layout, head folding, T padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_wkv.kernel import rwkv6_wkv_kernel


def rwkv6_wkv(r, k, v, w, u, s0, *, block_t=64, interpret=False):
    """r/k/v/w (B, T, H, N) f32; u (H, N); s0 (B, H, N, N)."""
    B, T, H, N = r.shape
    bt = min(block_t, T)
    pad = (-T) % bt

    def fold(x, fill=0.0):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, T, N)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)), constant_values=fill)
        return x.astype(jnp.float32)

    rf, kf, vf = fold(r), fold(k), fold(v)
    wf = fold(w, fill=1.0)   # padded steps: identity state update
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N).astype(jnp.float32)
    s0f = s0.reshape(B * H, N, N).astype(jnp.float32)
    y, s_fin = rwkv6_wkv_kernel(rf, kf, vf, wf, uf, s0f, block_t=bt,
                                interpret=interpret)
    y = y[:, :T].reshape(B, H, T, N).transpose(0, 2, 1, 3)
    return y, s_fin.reshape(B, H, N, N)

"""jit'd public wrapper: QuantizedTensor in, padding/tiling handled here.

The default (``interpret=False``) compiles the Mosaic kernel, which only a
TPU runs; CPU tests pass ``interpret=True`` to run the same kernel body in
the Pallas interpreter. No model path calls this yet: the model's
frozen-weight matmuls dequantize in XLA (``core.hetero.static_matmul``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedTensor
from repro.kernels.crossbar_matmul.kernel import (CROSSBAR, DEFAULT_BLOCK_M,
                                                  crossbar_matmul as _kernel)


def crossbar_matmul(x, qt: QuantizedTensor, *, block_m: int = DEFAULT_BLOCK_M,
                    interpret: bool = False, out_dtype=None):
    """x (..., K) @ qt (K, N) -> (..., N) via the Pallas crossbar kernel."""
    assert qt.ndim == 2, "2D weights (batched experts loop in the caller)"
    K, N = qt.orig_shape
    lead = x.shape[:-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    pad_m = (-M) % block_m
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    pk = qt.codes.shape[0] * (2 if qt.bits == 4 else 1)
    if pk != K:                      # quantizer padded K to a 128 multiple
        x2 = jnp.pad(x2, ((0, 0), (0, pk - K)))
    y = _kernel(x2, qt.codes, qt.scales, bits=qt.bits, block_m=block_m,
                interpret=interpret, out_dtype=out_dtype or x.dtype)
    pn = qt.codes.shape[1]
    if pn != N:
        y = y[:, :N]
    if pad_m:
        y = y[:M]
    return y.reshape(*lead, N)

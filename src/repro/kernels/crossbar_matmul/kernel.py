"""Pallas TPU kernel: crossbar-wise quantized matmul with post-accumulation
dequantization (Atleus SS IV.D, Fig. 5).

The ReRAM crossbar geometry (128x128 cells, one quantization scale per
crossbar, dequant applied to the *accumulated* MVM output by the extra
shift-and-add stage) maps 1:1 onto MXU tiling:

  * weights live in HBM as int8 codes (int4: two-per-byte packed along K)
    plus one f32 scale per (128,128) block — exactly the crossbar layout;
  * the grid walks (M/bm, N/bn, K/128); each step runs the (bm,128)x(128,bn)
    MXU pass on the *codes* and applies the block scale to the f32
    accumulator tile — dequantization after accumulation, once per
    crossbar, not per weight element (the GPU ordering the paper beats);
  * the f32 accumulator tile is VMEM-resident scratch across the K grid
    dimension (TPU grids execute the minor dimension sequentially).

Weight-stationary semantics: codes/scales are loop-invariant operands (the
"conductances"); only activations stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 256
CROSSBAR = 128  # ReRAM crossbar size == MXU tile == quantization block


def _kernel_int8(x_ref, codes_ref, scale_ref, out_ref, acc_ref, *, n_k, n_n):
    k = pl.program_id(2)
    scale = scale_ref[k * n_n + pl.program_id(1)]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (bm, 128)
    w = codes_ref[...].astype(jnp.float32)        # (128, bn) int8 codes
    partial = jnp.dot(x, w, preferred_element_type=jnp.float32)
    # post-MVM dequantization: one scale per 128x128 crossbar
    acc_ref[...] += partial * scale

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _kernel_int4(x_ref, codes_ref, scale_ref, out_ref, acc_ref, *, n_k, n_n):
    k = pl.program_id(2)
    scale = scale_ref[k * n_n + pl.program_id(1)]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (bm, 128)
    packed = codes_ref[...]                       # (64, bn) uint8, 2 nibbles
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    # unpack interleaved along K: rows (2i, 2i+1) <- (lo_i, hi_i)
    w = jnp.stack([lo, hi], axis=1).reshape(CROSSBAR, -1).astype(jnp.float32)
    partial = jnp.dot(x, w, preferred_element_type=jnp.float32)
    acc_ref[...] += partial * scale

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "block_n",
                                             "interpret", "out_dtype"))
def crossbar_matmul(x, codes, scales, *, bits: int = 8,
                    block_m: int = DEFAULT_BLOCK_M, block_n: int = CROSSBAR,
                    interpret: bool = False, out_dtype=None):
    """x (M, K) @ dequant(codes, scales) -> (M, N).

    codes: int8 (K, N) for 8-bit, uint8 (K//2, N) packed for 4-bit.
    scales: f32 (K/128, N/128). M, K, N must be multiples of the tile sizes
    (the ops wrapper pads)."""
    M, K = x.shape
    N = codes.shape[1]
    out_dtype = out_dtype or x.dtype
    assert M % block_m == 0 and N % block_n == 0 and K % CROSSBAR == 0
    assert block_n == CROSSBAR, "one scale per crossbar: bn == 128"
    n_k, n_n = K // CROSSBAR, N // block_n
    grid = (M // block_m, n_n, n_k)

    if bits == 8:
        kern = functools.partial(_kernel_int8, n_k=n_k, n_n=n_n)
        codes_spec = pl.BlockSpec((CROSSBAR, block_n), lambda i, j, k: (k, j))
    elif bits == 4:
        kern = functools.partial(_kernel_int4, n_k=n_k, n_n=n_n)
        codes_spec = pl.BlockSpec((CROSSBAR // 2, block_n), lambda i, j, k: (k, j))
    else:
        raise ValueError(bits)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, CROSSBAR), lambda i, j, k: (i, k)),
            codes_spec,
            # every crossbar scale, flat, in scalar memory: a (1, 1) VMEM
            # block of the (K/128, N/128) table is not a legal TPU tile
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, codes, scales.reshape(-1))

"""Heterogeneous compute mapping (Atleus SS IV.A, Eqs. 2-3, 5).

Every matrix multiplication in the model is classified by operand staticness:

  STATIC   — activation x *frozen* weight (MHA-1/MHA-4/FF-1/FF-2, mamba &
             rwkv projections). On Atleus these run on weight-stationary
             ReRAM crossbars; here they take the quantized crossbar path
             (``crossbar_matmul`` Pallas kernel on TPU, blockwise-dequant
             einsum under XLA) and are eligible for crossbar-wise
             quantization + noise injection.
  DYNAMIC  — activation x activation (MHA-2 QK^T, MHA-3 PV, ssm/rwkv
             recurrences) or activation x *trainable* weight (LoRA A/B).
             On Atleus these run on the OS-dataflow systolic array; here
             they stay on the bf16 MXU path (fused flash-attention kernel
             for MHA-2/3).

A trace-time tally (`tally()`) accumulates per-class FLOPs so tests and the
Fig. 7 benchmark can check the paper's Eq. 5 ratio (>90% of MM on the static
engine) directly against the model as built, not just analytically.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.noise import NoiseConfig, apply_weight_noise

Array = jax.Array

STATIC = "static"     # -> ReRAM / crossbar path
DYNAMIC = "dynamic"   # -> systolic / MXU bf16 path


class _Tally(threading.local):
    def __init__(self):
        self.active: Optional[Dict[str, float]] = None


_TALLY = _Tally()


@contextlib.contextmanager
def tally():
    """Collect per-engine-class FLOPs while tracing a function.

    Shapes are static under jit, so accumulating at trace time gives exact
    analytic counts for the traced computation."""
    prev = _TALLY.active
    _TALLY.active = {STATIC: 0.0, DYNAMIC: 0.0, "nonlinear": 0.0}
    try:
        yield _TALLY.active
    finally:
        _TALLY.active = prev


def _record(cls: str, flops: float) -> None:
    if _TALLY.active is not None:
        _TALLY.active[cls] += float(flops)


@contextlib.contextmanager
def repeated(n: int):
    """Count what is traced inside ``n`` times: a scan body is traced once
    and runs once per step of the scan."""
    outer = _TALLY.active
    if outer is None:
        yield
        return
    _TALLY.active = dict.fromkeys(outer, 0.0)
    try:
        yield
    finally:
        inner, _TALLY.active = _TALLY.active, outer
        for cls, v in inner.items():
            outer[cls] += n * v


def record_nonlinear(elements: int) -> None:
    """Softmax / layernorm / activation element counts (MHA-3, L-1, L-2)."""
    _record("nonlinear", float(elements))


def _matmul_flops(x_shape, w_shape) -> float:
    # batched x (..., m, k) @ w (..., k, n): 2*m*k*n * prod(batch)
    k, n = w_shape[-2], w_shape[-1]
    m = 1
    for d in x_shape[:-1]:
        m *= d
    return 2.0 * m * k * n


def static_matmul(x: Array, w, *, noise: Optional[NoiseConfig] = None,
                  rng: Optional[Array] = None, precision=None) -> Array:
    """Activation x frozen-weight matmul — the ReRAM/crossbar path.

    ``w`` may be a raw array or a ``QuantizedTensor`` (crossbar-wise
    quantized). Dequantization happens post-MVM on the hardware; under XLA
    we dequantize blockwise at use (memory traffic still reflects the low-bit
    residency since the codes are what lives in HBM/at rest)."""
    if quant.is_quantized(w):
        wd = quant.dequantize(w, x.dtype)
    else:
        wd = w.astype(x.dtype)
    if noise is not None and noise.enabled:
        wd = apply_weight_noise(wd, noise, rng)
    _record(STATIC, _matmul_flops(x.shape, wd.shape))
    return jax.lax.dot_general(
        x, wd, (((x.ndim - 1,), (wd.ndim - 2,)), ((), ())),
        precision=precision, preferred_element_type=x.dtype)


def static_einsum(spec: str, x: Array, w, *, noise: Optional[NoiseConfig] = None,
                  rng: Optional[Array] = None) -> Array:
    """Batched activation x frozen-weight einsum on the STATIC engine
    (expert matmuls: the expert/slot dim is a batch dim)."""
    if quant.is_quantized(w):
        wd = quant.dequantize(w, x.dtype)
    else:
        wd = w.astype(x.dtype)
    if noise is not None and noise.enabled:
        wd = apply_weight_noise(wd, noise, rng)
    _record(STATIC, _einsum_flops(spec, (x, wd)))
    return jnp.einsum(spec, x, wd, preferred_element_type=x.dtype)


def dynamic_matmul(x: Array, y: Array, *, contract=None, precision=None,
                   preferred_element_type=None) -> Array:
    """Dynamic-operand matmul — the systolic/MXU path (MHA-2/3, LoRA)."""
    if contract is None:
        contract = (((x.ndim - 1,), (y.ndim - 2,)), ((), ()))
    k = 1
    for d in contract[0][0]:
        k *= x.shape[d]
    m = x.size // k
    n = y.size // k // max(1, _batch_size(y, contract[1][1]))
    _record(DYNAMIC, 2.0 * m * k * n)
    return jax.lax.dot_general(x, y, contract, precision=precision,
                               preferred_element_type=preferred_element_type)


def _batch_size(y, batch_dims) -> int:
    b = 1
    for d in batch_dims:
        b *= y.shape[d]
    return b


def dynamic_einsum(spec: str, *operands, preferred_element_type=None) -> Array:
    """einsum on the DYNAMIC engine, with trace-time flop accounting."""
    _record(DYNAMIC, _einsum_flops(spec, operands))
    return jnp.einsum(spec, *operands,
                      preferred_element_type=preferred_element_type)


def _einsum_flops(spec: str, operands) -> float:
    inputs, out = spec.replace(" ", "").split("->")
    terms = inputs.split(",")
    dim_size: Dict[str, int] = {}
    for term, op in zip(terms, operands):
        for ch, s in zip(term, op.shape):
            dim_size[ch] = s
    total = 1
    for ch, s in dim_size.items():
        total *= s
    return 2.0 * total


@dataclass
class BreakdownReport:
    """Eq. 5 check: MM_ReRAM / MM_systolic for a traced step."""

    static_flops: float
    dynamic_flops: float
    nonlinear_elems: float

    @property
    def static_share(self) -> float:
        tot = self.static_flops + self.dynamic_flops
        return self.static_flops / tot if tot else 0.0

    @property
    def ratio(self) -> float:
        return self.static_flops / max(self.dynamic_flops, 1.0)


def breakdown_of(fn, *args, **kwargs) -> BreakdownReport:
    """Trace ``fn`` abstractly and report the engine-class breakdown."""
    with tally() as t:
        jax.eval_shape(fn, *args, **kwargs)
    return BreakdownReport(t[STATIC], t[DYNAMIC], t["nonlinear"])

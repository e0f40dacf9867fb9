"""Mamba selective-state-space block (jamba's 7-of-8 layers).

The projections (in/out/x/dt) are STATIC-engine matmuls (crossbar-
quantizable frozen weights); the selective scan itself is a dynamic
recurrence with no weight-stationary form — it runs on the DYNAMIC engine
(DESIGN.md SS5). On a TPU, an unsharded scan of at least one time block runs
as fused Pallas kernels, forward and backward (``kernels/selective_scan``),
with the state in VMEM. Elsewhere the scan is chunked: sequential
``lax.scan`` over chunks of ``cfg.mamba.chunk`` steps carrying the
(B, d_in, N) state, with a parallel ``associative_scan`` inside each chunk —
O(T) work, O(B*chunk*d_in*N) transient memory (d_in is TP-sharded so this
divides by the mesh width).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import hetero
from repro.core.noise import NoiseConfig
from repro.kernels.selective_scan import ops as ss_ops
from repro.models import layers

Array = jax.Array

# Per-slot decode-state leaves: the conv tail holds the last K-1 inputs and
# the SSM state is cumulative over the whole stream, both indexed by slot
# row (batch dim). The serving ``SlotStateArena`` snapshots / restores /
# zeroes them by slot id — a paged-KV cursor rewind cannot rewind them.
SLOT_STATE_LEAVES = ("conv", "ssm")


def init_mamba(cfg: ModelConfig, key: Array, dtype) -> Dict[str, Array]:
    mc = cfg.mamba
    d = cfg.d_model
    d_in = mc.expand * d
    r = mc.rank(d)
    N = mc.d_state
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[4], (d_in,)) *
                 (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": layers.dense_init(ks[0], (d, 2 * d_in), dtype),
        "conv_w": (0.1 * jax.random.normal(ks[5], (mc.d_conv, d_in))).astype(dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "x_proj": layers.dense_init(ks[1], (d_in, r + 2 * N), dtype, fan_in=d_in),
        "dt_proj": layers.dense_init(ks[2], (r, d_in), dtype, fan_in=r),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32),
                                          (d_in, N))).copy(),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": layers.dense_init(ks[3], (d_in, d), dtype, fan_in=d_in),
        # jamba's RMS norms on the time-step input, B and C
        "dt_norm": jnp.ones((r,), dtype),
        "b_norm": jnp.ones((N,), dtype),
        "c_norm": jnp.ones((N,), dtype),
    }


def _causal_conv(x: Array, w: Array, b: Array, state: Optional[Array],
                 valid_len: Optional[Array] = None) -> Tuple[Array, Array]:
    """Depthwise causal conv over time. x (B,T,C), w (K,C).
    ``state`` (B, K-1, C) carries the tail of the previous segment.
    ``valid_len`` (B,) marks ragged chunks: the emitted state is the tail of
    the last K-1 *valid* inputs per row, so padded tails never leak."""
    B, T, C = x.shape
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B, T+K-1, C)
    out = jnp.zeros_like(x, shape=(B, T, C))
    wf = w.astype(jnp.float32)
    acc = jnp.zeros((B, T, C), jnp.float32)
    for j in range(K):
        acc = acc + xp[:, j:j + T, :].astype(jnp.float32) * wf[j]
    out = acc + b.astype(jnp.float32)
    if K == 1:
        new_state = state
    elif valid_len is None:
        new_state = xp[:, T:, :]
    else:
        # valid inputs occupy xp rows [0, K-1+len); their K-1 tail starts
        # at row len (clipped so len==0 keeps the incoming state)
        start = jnp.clip(valid_len, 0, T)
        new_state = jax.vmap(
            lambda row, s: jax.lax.dynamic_slice(row, (s, 0), (K - 1, C))
        )(xp, start)
    hetero.record_nonlinear(x.size * K)
    return out.astype(x.dtype), new_state.astype(x.dtype)


def _selective_scan(dt: Array, Bc: Array, Cc: Array, xi: Array, A: Array,
                    h0: Array, chunk: int, sharder=None) -> Tuple[Array, Array]:
    """Selective scan: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t;
    y_t = C_t . h_t.  dt/xi (B,T,D) f32; Bc/Cc (B,T,N); A (D,N); h0 (B,D,N).

    Unsharded scans of at least one kernel time block go through
    ``fused_scan`` (the Pallas kernels on a TPU); decode steps, short
    chunks and a TP-sharded d_in keep ``_chunked_scan``."""
    if sharder is None and ss_ops.applies(dt.shape[1]):
        return fused_scan(dt, Bc, Cc, xi, A, h0, chunk)
    return _chunked_scan(dt, Bc, Cc, xi, A, h0, chunk, sharder)


def _chunked_scan(dt: Array, Bc: Array, Cc: Array, xi: Array, A: Array,
                  h0: Array, chunk: int, sharder=None) -> Tuple[Array, Array]:
    """The selective scan in XLA: sequential ``lax.scan`` over chunks, a
    parallel ``associative_scan`` inside each.

    The (B, chunk, D, N) decay/increment tensors are built *inside* the
    checkpointed chunk body (never materialized for the whole sequence) and
    the C-contraction happens in-chunk, so transient memory is
    O(B*chunk*D*N) and the backward saves only chunk-boundary states."""
    B, T, D = dt.shape
    N = A.shape[-1]
    L = min(chunk, T)
    pad = (-T) % L
    if pad:
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 -> identity step
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
        xi = jnp.pad(xi, ((0, 0), (0, pad), (0, 0)))
    nc = dt.shape[1] // L
    sh = sharder if sharder is not None else (lambda x, n: x)
    h0 = sh(h0, "ssm_state")

    def to_chunks(x):
        return sh(x.reshape(B, nc, L, x.shape[-1]).transpose(1, 0, 2, 3),
                  "ssm_chunks")

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    @jax.checkpoint
    def chunk_body(h, xs):
        dt_c, b_c, c_c, x_c = xs                      # (B, L, .)
        a = jnp.exp(dt_c[..., None] * A)              # (B, L, D, N)
        bx = (dt_c * x_c)[..., None] * b_c[:, :, None, :]
        cum_a, cum_b = jax.lax.associative_scan(combine, (a, bx), axis=1)
        h_all = cum_a * sh(h, "ssm_state")[:, None] + cum_b   # (B, L, D, N)
        y = hetero.dynamic_einsum("bldn,bln->bld", h_all, c_c)
        return sh(h_all[:, -1], "ssm_state"), y

    h_fin, ys = jax.lax.scan(chunk_body, h0,
                             (to_chunks(dt), to_chunks(Bc), to_chunks(Cc),
                              to_chunks(xi)))
    y = ys.transpose(1, 0, 2, 3).reshape(B, nc * L, D)
    return y[:, :T], h_fin


def _kernels_fwd(dt, Bc, Cc, xi, A, h0, interpret=False):
    y, h_fin, hs = ss_ops.scan_fwd(dt, Bc, Cc, xi, A, h0, interpret=interpret)
    return (y, h_fin), (dt, Bc, Cc, xi, A, h0, hs)


def _chunked_fwd(dt, Bc, Cc, xi, A, h0, chunk):
    """``_chunked_scan`` with residuals shaped as ``_kernels_fwd``'s; its
    backward recomputes from the inputs, so the block states are zeros."""
    hs = jnp.zeros(ss_ops.block_states_shape(dt.shape, A.shape[1]),
                   jnp.float32)
    return (_chunked_scan(dt, Bc, Cc, xi, A, h0, chunk),
            (dt, Bc, Cc, xi, A, h0, hs))


def _chunked_bwd(chunk, res, cts):
    # the forward was counted when the forward rule traced it
    with hetero.repeated(0):
        _, vjp = jax.vjp(functools.partial(_chunked_scan, chunk=chunk),
                         *res[:-1])
    return vjp(cts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def fused_scan(dt, Bc, Cc, xi, A, h0, chunk, interpret=False):
    """The selective scan as the fused Pallas kernels of
    ``kernels/selective_scan`` where the call is lowered for a TPU, else as
    ``_chunked_scan``. Each rule stages both and the lowering keeps one;
    the chunked path keeps the operation tally, counted by the forward
    rule only. ``interpret`` runs the kernels in the interpreter on any
    platform."""
    return _fused_fwd(dt, Bc, Cc, xi, A, h0, chunk, interpret)[0]


def _fused_fwd(dt, Bc, Cc, xi, A, h0, chunk, interpret):
    if interpret:
        return _kernels_fwd(dt, Bc, Cc, xi, A, h0, interpret=True)
    return jax.lax.platform_dependent(
        dt, Bc, Cc, xi, A, h0, tpu=_kernels_fwd,
        default=functools.partial(_chunked_fwd, chunk=chunk))


def _fused_bwd(chunk, interpret, res, cts):
    def kernels(res, cts, interpret=False):
        dt, Bc, Cc, xi, A, _, hs = res
        return ss_ops.scan_bwd(dt, Bc, Cc, xi, A, hs, *cts,
                               interpret=interpret)

    # the rule is traced outside ``apply_mamba_block``'s precision context
    with jax.default_matmul_precision("highest"):
        if interpret:
            return kernels(res, cts, interpret=True)
        return jax.lax.platform_dependent(
            res, cts, tpu=kernels,
            default=functools.partial(_chunked_bwd, chunk))


fused_scan.defvjp(_fused_fwd, _fused_bwd)


def apply_mamba_block(
    cfg: ModelConfig, p: Dict[str, Array], x: Array, *,
    cache: Optional[Dict[str, Array]] = None,
    lora: Optional[Dict] = None, adapter_idx=None,
    noise: Optional[NoiseConfig] = None, rng: Optional[Array] = None,
    sharder=None, chunk_lens: Optional[Array] = None,
) -> Tuple[Array, Optional[Dict[str, Array]]]:
    """x (B,T,d) -> (y, new_cache). cache: {conv (B,K-1,d_in), ssm (B,d_in,N)}.

    ``chunk_lens`` (B,) marks ragged decode chunks: rows are only valid for
    their first ``chunk_lens[b]`` tokens. Padded steps run with dt == 0 (an
    identity state transition), so the SSM state a row emits is exactly the
    state after its last valid token.

    Every product of the block, forward and backward, runs at ``HIGHEST``
    precision: the scan compounds the rounding of what feeds it over the
    whole sequence. On a TPU v5e at the default (one bfloat16 pass),
    jamba2-3b's LoRA gradients over one row of 8192 tokens drifted from
    float32 by up to 9.0e-3 of a leaf's norm; with this block at
    ``HIGHEST``, by 4.7e-4, for 3.6% more step time."""
    with jax.default_matmul_precision("highest"):
        return _mamba_block(cfg, p, x, cache=cache, lora=lora,
                            adapter_idx=adapter_idx, noise=noise, rng=rng,
                            sharder=sharder, chunk_lens=chunk_lens)


def _mamba_block(cfg, p, x, *, cache, lora, adapter_idx, noise, rng,
                 sharder, chunk_lens):
    from repro.core.lora import lora_delta, lora_scale

    mc = cfg.mamba
    B, T, d = x.shape
    d_in = mc.expand * d
    N = mc.d_state
    r = mc.rank(d)

    xz = hetero.static_matmul(x, p["in_proj"], noise=noise, rng=rng)
    if lora is not None and "mamba_in" in lora:
        xz = xz + lora_delta(x, lora["mamba_in"], lora_scale(cfg), adapter_idx)
    xi, z = jnp.split(xz, 2, axis=-1)

    conv_state = cache["conv"] if cache is not None else None
    with jax.named_scope(obs.SSM_SCAN):
        xi, new_conv = _causal_conv(xi, p["conv_w"], p["conv_b"], conv_state,
                                    valid_len=chunk_lens)
        xi = jax.nn.silu(xi)
    hetero.record_nonlinear(xi.size)

    dbc = hetero.static_matmul(xi, p["x_proj"], noise=noise, rng=rng)
    dt_r, Bc, Cc = jnp.split(dbc, [r, r + N], axis=-1)
    dt_r = layers.rms_norm(dt_r, p["dt_norm"], cfg.norm_eps)
    Bc = layers.rms_norm(Bc, p["b_norm"], cfg.norm_eps)
    Cc = layers.rms_norm(Cc, p["c_norm"], cfg.norm_eps)
    dt = hetero.static_matmul(dt_r, p["dt_proj"], noise=noise, rng=rng)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])      # (B,T,d_in)
    if chunk_lens is not None:
        # padded tail steps become identity transitions (dt=0 -> a=1, bx=0)
        valid = jnp.arange(T)[None, :] < chunk_lens[:, None]
        dt = dt * valid[:, :, None]
    A = -jnp.exp(p["A_log"])                                         # (d_in, N)
    hetero.record_nonlinear(dt.size * 2 * N)

    h0 = (cache["ssm"].astype(jnp.float32) if cache is not None
          else jnp.zeros((B, d_in, N), jnp.float32))
    with jax.named_scope(obs.SSM_SCAN):
        y, h_fin = _selective_scan(dt, Bc.astype(jnp.float32),
                                   Cc.astype(jnp.float32),
                                   xi.astype(jnp.float32), A, h0, mc.chunk,
                                   sharder=sharder)
        y = y + p["D"] * xi.astype(jnp.float32)
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    hetero.record_nonlinear(y.size)

    out = hetero.static_matmul(y, p["out_proj"], noise=noise, rng=rng)
    if lora is not None and "mamba_out" in lora:
        out = out + lora_delta(y, lora["mamba_out"], lora_scale(cfg), adapter_idx)

    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "ssm": h_fin.astype(cache["ssm"].dtype)}
    return out, new_cache

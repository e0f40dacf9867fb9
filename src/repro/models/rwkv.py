"""RWKV6 "Finch" block (rwkv6-7b): attention-free time-mix with
data-dependent decay + channel-mix.

Paper-technique mapping (DESIGN.md SS5): all projections (r/k/v/g/o,
channel-mix) are STATIC-engine frozen weights and crossbar-quantize fine;
the wkv recurrence (state S in R^{H x N x N} with per-token decay w_t) is a
dynamic recurrence -> DYNAMIC engine. The recurrence runs as a sequential
``lax.scan`` over time, vectorized over (B, H, N, N); the TPU Pallas kernel
(`repro.kernels.rwkv6_wkv`) keeps the state VMEM-resident (the
output-stationary dataflow analogue).

Recurrence (official Finch form), per head, N = head_dim:
    y_t     = r_t · (S_t + u ⊙ (k_t ⊗ v_t))
    S_{t+1} = diag(w_t) S_t + k_t ⊗ v_t
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import hetero
from repro.core.noise import NoiseConfig
from repro.models import layers

Array = jax.Array

MIX_NAMES = ("r", "w", "k", "v", "g")

# Per-slot decode-state leaves: token-shift buffers hold the previous
# token's activations and the wkv matrix accumulates over the whole
# stream, all indexed by slot row (batch dim). The serving
# ``SlotStateArena`` snapshots / restores / zeroes them by slot id — a
# paged-KV cursor rewind cannot rewind them.
SLOT_STATE_LEAVES = ("shift_t", "shift_c", "wkv")


def init_rwkv(cfg: ModelConfig, key: Array, dtype) -> Dict[str, Array]:
    rc = cfg.rwkv
    d = cfg.d_model
    H = d // rc.head_dim
    ks = jax.random.split(key, 16)
    ratio = jnp.arange(d, dtype=jnp.float32) / d
    p = {
        "ln1": {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
        "ln2": {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
        "time_mix": {
            "mu": jnp.stack([1.0 - ratio ** (0.3 + 0.1 * i) for i in range(5)]).astype(dtype),
            "mu_x": (1.0 - ratio ** 0.3).astype(dtype),
            "w_mix_a": layers.dense_init(ks[0], (d, 5 * rc.mix_lora), dtype),
            "w_mix_b": (0.02 * jax.random.normal(ks[1], (5, rc.mix_lora, d))).astype(dtype),
            "w_base": (-6.0 + 5.0 * ratio).astype(jnp.float32),
            "w_lora_a": layers.dense_init(ks[2], (d, rc.decay_lora), dtype),
            "w_lora_b": (0.02 * jax.random.normal(ks[3], (rc.decay_lora, d))).astype(dtype),
            "u": (0.5 * jnp.ones((H, rc.head_dim))).astype(jnp.float32),
            "r_proj": layers.dense_init(ks[4], (d, d), dtype),
            "k_proj": layers.dense_init(ks[5], (d, d), dtype),
            "v_proj": layers.dense_init(ks[6], (d, d), dtype),
            "g_proj": layers.dense_init(ks[7], (d, d), dtype),
            "o_proj": layers.dense_init(ks[8], (d, d), dtype),
            "ln_x": {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
        },
        "channel_mix": {
            "mu_k": (1.0 - ratio ** 0.3).astype(dtype),
            "mu_r": (1.0 - ratio ** 0.3).astype(dtype),
            "ck_proj": layers.dense_init(ks[9], (d, cfg.d_ff), dtype),
            "cv_proj": layers.dense_init(ks[10], (cfg.d_ff, d), dtype, fan_in=cfg.d_ff),
            "cr_proj": layers.dense_init(ks[11], (d, d), dtype),
        },
    }
    return p


def _token_shift(x: Array, prev: Optional[Array]) -> Array:
    """xx_t = x_{t-1}; first step uses ``prev`` (decode cache) or zeros."""
    B, T, d = x.shape
    first = jnp.zeros((B, 1, d), x.dtype) if prev is None else prev[:, None, :].astype(x.dtype)
    return jnp.concatenate([first, x[:, :-1, :]], axis=1) if T > 1 else first


def wkv_scan(r: Array, k: Array, v: Array, w: Array, u: Array, s0: Array,
             chunk: int = 64, sharder=None) -> Tuple[Array, Array]:
    """Sequential wkv recurrence, chunk-checkpointed.

    r/k/v/w (B,T,H,N) f32; u (H,N); s0 (B,H,N,N). Returns y (B,T,H,N),
    s_final. The scan over time is grouped into chunks whose bodies are
    ``jax.checkpoint``ed: the backward pass saves only chunk-boundary
    states (T/chunk x B*H*N*N) and recomputes the per-step states within
    one chunk at a time — without this, autodiff saves the full (T, B, H,
    N, N) state history (16 GiB/device at T=4096 for rwkv6-7b)."""
    hetero.record_nonlinear(r.size)
    hetero._record(hetero.DYNAMIC, 4.0 * r.shape[0] * r.shape[1] *
                   r.shape[2] * r.shape[3] ** 2)
    B, T, H, N = r.shape
    sh = sharder if sharder is not None else (lambda x, n: x)
    s0 = sh(s0, "wkv_state")

    def step(s, rkvw):
        rt, kt, vt, wt = rkvw                      # (B,H,N)
        kv = kt[..., :, None] * vt[..., None, :]   # (B,H,N,N)
        y = jnp.einsum("bhi,bhij->bhj", rt, s + u[..., :, None] * kv)
        s = wt[..., :, None] * s + kv
        return s, y

    if T == 1:
        s_fin, y = step(s0, (r[:, 0], k[:, 0], v[:, 0], w[:, 0]))
        return y[:, None], s_fin

    L = min(chunk, T)
    pad = (-T) % L
    def to_chunks(x):
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nc = xp.shape[1] // L
        return xp.reshape(B, nc, L, H, N).transpose(1, 2, 0, 3, 4)  # (nc,L,B,H,N)

    rc, kc, vc, wc = (sh(to_chunks(x), "wkv_chunks") for x in (r, k, v, w))
    # padded steps: w=1, k=0 -> state unchanged
    if pad:
        valid = (jnp.arange(rc.shape[0] * L) < T).reshape(rc.shape[0], L)
        m = valid[:, :, None, None, None]
        kc = jnp.where(m, kc, 0.0)
        wc = jnp.where(m, wc, 1.0)

    @jax.checkpoint
    def chunk_fn(s, rkvw_c):
        with jax.named_scope("wkv_fused"):
            s, ys = jax.lax.scan(step, sh(s, "wkv_state"), rkvw_c)
        return sh(s, "wkv_state"), ys

    s_fin, ys = jax.lax.scan(chunk_fn, s0, (rc, kc, vc, wc))  # ys (nc,L,B,H,N)
    y = ys.transpose(2, 0, 1, 3, 4).reshape(B, -1, H, N)[:, :T]
    return y, s_fin


def apply_rwkv_block(
    cfg: ModelConfig, p: Dict[str, Array], x: Array, *,
    cache: Optional[Dict[str, Array]] = None,
    lora: Optional[Dict] = None, adapter_idx=None,
    noise: Optional[NoiseConfig] = None, rng: Optional[Array] = None,
    impl: str = "auto", sharder=None,
    chunk_lens: Optional[Array] = None,
) -> Tuple[Array, Optional[Dict[str, Array]]]:
    """Full RWKV6 block: x + time_mix(ln1(x)); then + channel_mix(ln2(.)).

    cache: {shift_t (B,d), shift_c (B,d), wkv (B,H,N,N) f32}.

    ``chunk_lens`` (B,) marks ragged decode chunks: padded steps run the
    wkv recurrence with k=0, w=1 (state unchanged) and the emitted shift
    states come from each row's last *valid* token."""
    from repro.core.lora import lora_delta, lora_scale

    rc = cfg.rwkv
    tm = p["time_mix"]
    B, T, d = x.shape
    H, N = d // rc.head_dim, rc.head_dim
    scale = lora_scale(cfg)

    # ---------------- time mix ----------------
    with jax.named_scope(obs.RECURRENT):
        xn = layers.layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], cfg.norm_eps)
        xx = _token_shift(xn, cache["shift_t"] if cache is not None else None)
        diff = xx - xn
        # dynamic token-shift mixing (the "ddd" lora)
        xmix = xn + diff * tm["mu_x"]
        ddd = jnp.tanh(hetero.static_matmul(xmix, tm["w_mix_a"]))
        ddd = ddd.reshape(B, T, 5, rc.mix_lora)
        dyn = hetero.dynamic_einsum("btfr,frd->btfd", ddd,
                                    tm["w_mix_b"].astype(x.dtype))
        mixed = {}
        for i, name in enumerate(MIX_NAMES):
            mixed[name] = xn + diff * (tm["mu"][i] + dyn[:, :, i, :])

        def proj(name, target):
            y = hetero.static_matmul(mixed[name], tm[f"{name}_proj"],
                                     noise=noise, rng=rng)
            if lora is not None and target in lora:
                y = y + lora_delta(mixed[name], lora[target], scale, adapter_idx)
            return y

        r = proj("r", "wq").reshape(B, T, H, N).astype(jnp.float32)
        k = proj("k", "wk").reshape(B, T, H, N).astype(jnp.float32)
        v = proj("v", "wv").reshape(B, T, H, N).astype(jnp.float32)
        g = jax.nn.silu(hetero.static_matmul(mixed["g"], tm["g_proj"],
                                             noise=noise, rng=rng))

        # data-dependent decay w_t in (0, 1)
        w_raw = tm["w_base"] + hetero.dynamic_matmul(
            jnp.tanh(hetero.static_matmul(mixed["w"], tm["w_lora_a"])),
            tm["w_lora_b"].astype(x.dtype)).astype(jnp.float32)
        w = jnp.exp(-jnp.exp(w_raw)).reshape(B, T, H, N)
        hetero.record_nonlinear(w.size * 2)

        if chunk_lens is not None:
            # padded steps: k=0, w=1 -> wkv state passes through unchanged
            valid = (jnp.arange(T)[None, :] < chunk_lens[:, None])[..., None, None]
            k = jnp.where(valid, k, 0.0)
            w = jnp.where(valid, w, 1.0)

        s0 = (cache["wkv"].astype(jnp.float32) if cache is not None
              else jnp.zeros((B, H, N, N), jnp.float32))
        if impl == "pallas":
            from repro.kernels.rwkv6_wkv import ops as wkv_ops
            y, s_fin = wkv_ops.rwkv6_wkv(r, k, v, w, tm["u"], s0)
        else:
            y, s_fin = wkv_scan(r, k, v, w, tm["u"], s0, sharder=sharder)

        # per-head groupnorm, gate, output proj
        yf = y.reshape(B, T, H, N)
        mu = jnp.mean(yf, axis=-1, keepdims=True)
        var = jnp.var(yf, axis=-1, keepdims=True)
        yf = (yf - mu) * jax.lax.rsqrt(var + 64e-5)
        yf = yf.reshape(B, T, d) * p["time_mix"]["ln_x"]["scale"] + tm["ln_x"]["bias"]
        hetero.record_nonlinear(yf.size)
        att = hetero.static_matmul((yf.astype(x.dtype) * g), tm["o_proj"],
                                   noise=noise, rng=rng)
        if lora is not None and "wo" in lora:
            att = att + lora_delta(yf.astype(x.dtype) * g, lora["wo"], scale,
                                   adapter_idx)
        x = x + att

    # ---------------- channel mix ----------------
    with jax.named_scope(obs.MLP):
        cm = p["channel_mix"]
        xn2 = layers.layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], cfg.norm_eps)
        xx2 = _token_shift(xn2, cache["shift_c"] if cache is not None else None)
        xk = xn2 + (xx2 - xn2) * cm["mu_k"]
        xr = xn2 + (xx2 - xn2) * cm["mu_r"]
        kf = hetero.static_matmul(xk, cm["ck_proj"], noise=noise, rng=rng)
        kf = jnp.square(jax.nn.relu(kf))
        hetero.record_nonlinear(kf.size)
        vf = hetero.static_matmul(kf, cm["cv_proj"], noise=noise, rng=rng)
        rg = jax.nn.sigmoid(hetero.static_matmul(xr, cm["cr_proj"],
                                                 noise=noise, rng=rng))
        x = x + rg * vf

    new_cache = None
    if cache is not None:
        if chunk_lens is None:
            shift_t, shift_c = xn[:, -1, :], xn2[:, -1, :]
        else:
            last = jnp.clip(chunk_lens - 1, 0, T - 1)[:, None, None]
            shift_t = jnp.take_along_axis(xn, last, axis=1)[:, 0]
            shift_c = jnp.take_along_axis(xn2, last, axis=1)[:, 0]
            # rows with an empty chunk keep their incoming shift state
            alive = (chunk_lens > 0)[:, None]
            shift_t = jnp.where(alive, shift_t, cache["shift_t"].astype(shift_t.dtype))
            shift_c = jnp.where(alive, shift_c, cache["shift_c"].astype(shift_c.dtype))
        new_cache = {
            "shift_t": shift_t,
            "shift_c": shift_c,
            "wkv": s_fin.astype(cache["wkv"].dtype),
        }
    return x, new_cache

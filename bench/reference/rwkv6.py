"""Plain float32 reference of RWKV-6 "Finch" (arXiv:2404.05892).

Per layer, as published: a time mix with data-dependent token shift
(``x + (shift(x) - x) * (mu + tanh(xmix A) B)`` for r, w, k, v, g), the
data-dependent decay ``w = exp(-exp(w0 + tanh(x_w A_w) B_w))``, the wkv
recurrence per head ``y_t = r_t (S_t + u * k_t^T v_t)``,
``S_{t+1} = diag(w_t) S_t + k_t^T v_t`` from a zero state, a per-head group
norm (eps 64e-5), the gate ``silu(x_g W_g)`` and the output projection; then
the channel mix ``sigmoid(x_r W_r) * (relu(x_k W_k)^2 W_v)``. Layer norms
before each mix and before the untied head. LoRA adds ``(alpha/r) (h A) B``
to the receptance (wq) and value (wv) projections. The recurrence is a plain
``lax.scan`` over time; every product runs at ``Precision.HIGHEST``.

Departures, noted: the ``ln0`` norm that the published model applies to the
embeddings is taken as folded into the embedding table, as RWKV inference
code does at load time (the program has no ``ln0``). The five token-shift
mixes are stored in the order r, w, k, v, g.

This file imports nothing of the program under test; it makes the weights
both sides use, in the layout the program takes.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.reference import common
from bench.reference.common import F32, HIGHEST, act, mat, mm

PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "layer_norm_epsilon": "norm_eps",
    "head_size": "rwkv.head_dim",
    "time_mix_extra_dim": "rwkv.mix_lora",
    "time_decay_extra_dim": "rwkv.decay_lora",
}
MIXES = ("r", "w", "k", "v", "g")
LORA_TARGET = {"wq": "r", "wk": "k", "wv": "v"}


def dims(c: dict) -> Dict[str, int]:
    d, N = c["hidden_size"], c["head_size"]
    return dict(L=c["num_hidden_layers"], d=d, ff=c["intermediate_size"],
                V=c["vocab_size"], N=N, H=d // N,
                mix=c["time_mix_extra_dim"], decay=c["time_decay_extra_dim"])


def make_weights(c: dict, key) -> dict:
    """Seeded weights (call under ``jax.jit``): bfloat16, except the decay
    base and the bonus ``u``, float32 as the program keeps them."""
    n = dims(c)
    L, d, ff, V, H, N = n["L"], n["d"], n["ff"], n["V"], n["H"], n["N"]
    keys = iter(jax.random.split(key, 32))
    bf = jnp.bfloat16

    def normal(shape, std, dtype=bf):
        return (std * jax.random.normal(next(keys), shape, F32)).astype(dtype)

    def unit(shape):
        return jax.random.uniform(next(keys), shape, F32).astype(bf)

    def norm():
        return {"scale": (1 + 0.1 * jax.random.normal(next(keys), (L, d))
                          ).astype(bf),
                "bias": normal((L, d), 0.1)}

    return {
        "embed": {"table": normal((V, d), 1.0),
                  "unembed": normal((d, V), d ** -0.5)},
        "final_norm": {"scale": (1 + 0.1 * jax.random.normal(next(keys),
                                                              (d,))).astype(bf),
                       "bias": normal((d,), 0.1)},
        "layers": ({
            "ln1": norm(), "ln2": norm(),
            "time_mix": {
                "mu": unit((L, 5, d)), "mu_x": unit((L, d)),
                "w_mix_a": normal((L, d, 5 * n["mix"]), d ** -0.5),
                "w_mix_b": normal((L, 5, n["mix"], d), 0.1 * n["mix"] ** -0.5),
                "w_base": -6.0 + 5.0 * jax.random.uniform(next(keys), (L, d)),
                "w_lora_a": normal((L, d, n["decay"]), d ** -0.5),
                "w_lora_b": normal((L, n["decay"], d),
                                   0.1 * n["decay"] ** -0.5),
                "u": 0.5 + normal((L, H, N), 0.1, F32),
                "r_proj": normal((L, d, d), d ** -0.5),
                "k_proj": normal((L, d, d), d ** -0.5),
                "v_proj": normal((L, d, d), d ** -0.5),
                "g_proj": normal((L, d, d), d ** -0.5),
                "o_proj": normal((L, d, d), d ** -0.5),
                "ln_x": norm(),
            },
            "channel_mix": {
                "mu_k": unit((L, d)), "mu_r": unit((L, d)),
                "ck_proj": normal((L, d, ff), d ** -0.5),
                "cv_proj": normal((L, ff, d), ff ** -0.5),
                "cr_proj": normal((L, d, d), d ** -0.5),
            },
        },),
    }


def lora_shapes(c: dict) -> Dict[str, tuple]:
    n = dims(c)
    r = c["lora"]["rank"]
    return {t: ((n["L"], n["d"], r), (n["L"], r, n["d"]))
            for t in c["lora"]["targets"]}


def make_adapter(c: dict, key) -> dict:
    return common.lora_adapter(lora_shapes(c), key)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def wkv(r, k, v, w, u):
    """The recurrence from a zero state; r/k/v/w (T, H, N), u (H, N)."""
    H, N = u.shape

    def step(S, xs):
        rt, kt, vt, wt = xs
        kv = kt[:, :, None] * vt[:, None, :]                  # (H, N, N)
        y = jnp.einsum("hi,hij->hj", rt, S + u[:, :, None] * kv,
                       precision=HIGHEST)
        return wt[:, :, None] * S + kv, y

    _, y = jax.lax.scan(step, jnp.zeros((H, N, N), F32), (r, k, v, w))
    return y


def _layer(c, ctrl, x, lw, la):
    n = dims(c)
    T, H, N = x.shape[0], n["H"], n["N"]
    eps, sc = c["layer_norm_epsilon"], c["lora"]["alpha"] / c["lora"]["rank"]
    tm, cm = lw["time_mix"], lw["channel_mix"]

    xn = common.layer_norm(x, lw["ln1"], eps)
    xx = _shift(xn) - xn
    xmix = xn + xx * tm["mu_x"].astype(F32)
    ddd = jnp.tanh(mm(xmix, mat(tm["w_mix_a"], ctrl), ctrl)
                   ).reshape(T, 5, -1)
    dyn = jnp.einsum("tfr,frd->tfd", ddd, mat(tm["w_mix_b"], ctrl),
                     precision=HIGHEST)
    mixed = {m: xn + xx * (tm["mu"][i].astype(F32) + dyn[:, i])
             for i, m in enumerate(MIXES)}

    def proj(m, weight):
        y = mm(mixed[m], mat(tm[weight], ctrl), ctrl)
        for t, src in LORA_TARGET.items():
            if src == m and t in la:
                y = act(y + sc * mm(mm(mixed[m], la[t]["a"], ctrl),
                                    la[t]["b"], ctrl), ctrl)
        return y

    r = proj("r", "r_proj").reshape(T, H, N)
    k = proj("k", "k_proj").reshape(T, H, N)
    v = proj("v", "v_proj").reshape(T, H, N)
    g = jax.nn.silu(mm(mixed["g"], mat(tm["g_proj"], ctrl), ctrl))
    w_raw = tm["w_base"] + mm(jnp.tanh(mm(mixed["w"],
                                          mat(tm["w_lora_a"], ctrl), ctrl)),
                              mat(tm["w_lora_b"], ctrl), ctrl)
    w = jnp.exp(-jnp.exp(w_raw)).reshape(T, H, N)
    y = wkv(r, k, v, w, tm["u"])
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), -1, keepdims=True)
    y = ((y - mu) * jax.lax.rsqrt(var + 64e-5)).reshape(T, -1)
    y = y * tm["ln_x"]["scale"].astype(F32) + tm["ln_x"]["bias"].astype(F32)
    out = mm(y * g, mat(tm["o_proj"], ctrl), ctrl)
    if "wo" in la:
        out = out + sc * mm(mm(y * g, la["wo"]["a"], ctrl), la["wo"]["b"],
                            ctrl)
    x = act(x + out, ctrl)

    xn2 = common.layer_norm(x, lw["ln2"], eps)
    xx2 = _shift(xn2) - xn2
    xk = xn2 + xx2 * cm["mu_k"].astype(F32)
    xr = xn2 + xx2 * cm["mu_r"].astype(F32)
    kf = jnp.square(jax.nn.relu(mm(xk, mat(cm["ck_proj"], ctrl), ctrl)))
    return act(x + jax.nn.sigmoid(mm(xr, mat(cm["cr_proj"], ctrl), ctrl))
               * mm(kf, mat(cm["cv_proj"], ctrl), ctrl), ctrl)


def hidden(c: dict, w: dict, adapter: Optional[dict], tokens, ctrl=None):
    """Final-norm hidden states (T, d) of one sequence."""
    x = common.embed(w, tokens, ctrl)
    la = adapter["layers"][0] if adapter is not None else {}

    @jax.checkpoint
    def body(x, xs):
        lw, lad = xs
        return _layer(c, ctrl, x, lw, lad), None

    x, _ = jax.lax.scan(body, x, (w["layers"][0], la))
    return act(common.layer_norm(x, w["final_norm"], c["layer_norm_epsilon"]),
               ctrl)


def Reference(c: dict) -> common.Reference:
    return common.Reference(c, hidden)


# ---------------------------------------------------------------------------
# model FLOPs, counted from shapes
# ---------------------------------------------------------------------------

def _matmul_params(c: dict) -> tuple:
    """(matmul weights per layer, LoRA weights per layer, head)."""
    n = dims(c)
    d = n["d"]
    layer = (5 * d * d + 2 * 5 * n["mix"] * d + 2 * n["decay"] * d
             + 2 * d * n["ff"] + d * d)
    lora = sum(a[1] * a[2] + b[1] * b[2] for a, b in lora_shapes(c).values())
    return layer, lora, d * n["V"]


def wkv_flops_per_token(c: dict) -> float:
    """Per head and token: k^T v, u * kv, S + that, r (...) (multiply and
    add), w * S, + kv: 7 N^2."""
    n = dims(c)
    return 7.0 * n["H"] * n["N"] ** 2 * n["L"]


def serve_step_flops(c: dict, lens, clens) -> float:
    """Model FLOPs of one mixed step's real tokens: the layers' matmuls
    (2 per weight) and the recurrence for every real token, the head once
    per row."""
    import numpy as np
    n = dims(c)
    layer, lora, head = _matmul_params(c)
    clens = np.asarray(clens, np.float64)
    return float((2 * n["L"] * (layer + lora) + wkv_flops_per_token(c))
                 * clens.sum() + 2 * head * (clens > 0).sum())


def train_step_flops(c: dict, rows: int, T: int) -> float:
    """Model FLOPs of one LoRA step: forward and activation gradients of
    the matmuls (2 each per weight and token, head included), LoRA weight
    gradients, and the recurrence forward and backward (three times)."""
    n = dims(c)
    layer, lora, head = _matmul_params(c)
    per_token = (4 * (n["L"] * layer + head) + 6 * n["L"] * lora
                 + 3 * wkv_flops_per_token(c))
    return float(rows * T * per_token)

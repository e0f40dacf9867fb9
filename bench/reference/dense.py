"""Plain float32 reference of a dense decoder with grouped-query attention.

Mistral-NeMo as published (hf mistralai/Mistral-Nemo-Base-2407,
``MistralForCausalLM``): token embedding, then per layer
``x += Wo attn(rope(Wq rms(x)), rope(Wk rms(x)), Wv rms(x))`` with causal
grouped-query softmax attention, rotate-half RoPE of base ``rope_theta``, and
``x += W2 (silu(W1 rms(x)) * W3 rms(x))``; a final RMS norm and an untied
head. LoRA adds ``(alpha/r) (h A) B`` to the projections it targets. Every
matrix product runs at ``Precision.HIGHEST`` in float32 from the stored
weights; nothing is fused, cached or batched across requests.

This file imports nothing of the program under test. It also makes the
weights both sides use, from the seed, in the layout the program takes (a
checkpoint's layout: layer weights stacked along a leading layer axis).

The controls (``ctrl``, see ``common``) are the same computation one
precision step down: ``"bf16"`` with bfloat16 activations, products and KV;
``"fp8"`` with every base weight matrix rounded to float8 e4m3 under one
scale per tensor.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.reference import common
from bench.reference.common import F32, act, mat, mm

# configuration key -> field of the program's model config
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "attn.rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def dims(c: dict) -> Dict[str, int]:
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"],
                H=c["num_attention_heads"], Hkv=c["num_key_value_heads"],
                hd=c["head_dim"], ff=c["intermediate_size"],
                V=c["vocab_size"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def make_weights(c: dict, key) -> dict:
    """Seeded bfloat16 weights (call under ``jax.jit``). Norm scales are
    drawn around 1 so that a dropped scale shows."""
    n = dims(c)
    L, d, ff, V = n["L"], n["d"], n["ff"], n["V"]
    q, kv = n["H"] * n["hd"], n["Hkv"] * n["hd"]
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (std * jax.random.normal(next(keys), shape, F32)
                ).astype(jnp.bfloat16)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape, F32)
                ).astype(jnp.bfloat16)

    return {
        "embed": {"table": normal((V, d), 1.0),
                  "unembed": normal((d, V), d ** -0.5)},
        "final_norm": {"scale": scale((d,))},
        "layers": ({
            "norm": {"scale": scale((L, d))},
            "norm2": {"scale": scale((L, d))},
            "attn": {"wq": normal((L, d, q), d ** -0.5),
                     "wk": normal((L, d, kv), d ** -0.5),
                     "wv": normal((L, d, kv), d ** -0.5),
                     "wo": normal((L, q, d), q ** -0.5)},
            "ff": {"w1": normal((L, d, ff), d ** -0.5),
                   "w3": normal((L, d, ff), d ** -0.5),
                   "w2": normal((L, ff, d), ff ** -0.5)},
        },),
    }


def lora_shapes(c: dict) -> Dict[str, tuple]:
    n = dims(c)
    out = {"wq": n["H"] * n["hd"], "wk": n["Hkv"] * n["hd"],
           "wv": n["Hkv"] * n["hd"], "wo": n["d"]}
    din = {"wq": n["d"], "wk": n["d"], "wv": n["d"], "wo": n["H"] * n["hd"]}
    r = c["lora"]["rank"]
    return {t: ((n["L"], din[t], r), (n["L"], r, out[t]))
            for t in c["lora"]["targets"]}


def make_adapter(c: dict, key) -> dict:
    return common.lora_adapter(lora_shapes(c), key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(F32))


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..T-1; x (T, heads, hd)."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    s, co = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * co - x2 * s, x2 * co + x1 * s], -1)


def _layer(c, ctrl, x, lw, la):
    n = dims(c)
    T = x.shape[0]
    H, Hkv, hd = n["H"], n["Hkv"], n["hd"]
    eps, sc = c["rms_norm_eps"], c["lora"]["alpha"] / c["lora"]["rank"]

    def proj(h, name, group):
        y = mm(h, mat(lw[group][name], ctrl), ctrl)
        if name in la:
            y = act(y + sc * mm(mm(h, la[name]["a"], ctrl), la[name]["b"],
                                ctrl), ctrl)
        return y

    h = act(_rms(x, lw["norm"]["scale"], eps), ctrl)
    q = act(_rope(proj(h, "wq", "attn").reshape(T, H, hd), c["rope_theta"]),
            ctrl)
    k = act(_rope(proj(h, "wk", "attn").reshape(T, Hkv, hd), c["rope_theta"]),
            ctrl)
    v = proj(h, "wv", "attn").reshape(T, Hkv, hd)
    qg = q.reshape(T, Hkv, H // Hkv, hd)
    s = act(mm(qg.transpose(1, 2, 0, 3), k.transpose(1, 2, 0)[:, None],
               ctrl) * hd ** -0.5, ctrl)                     # (Hkv, g, T, T)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = act(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), ctrl)
    o = mm(p, v.transpose(1, 0, 2)[:, None], ctrl).transpose(2, 0, 1, 3)
    x = act(x + proj(o.reshape(T, H * hd), "wo", "attn"), ctrl)
    h2 = act(_rms(x, lw["norm2"]["scale"], eps), ctrl)
    g = act(jax.nn.silu(mm(h2, mat(lw["ff"]["w1"], ctrl), ctrl)), ctrl)
    u = mm(h2, mat(lw["ff"]["w3"], ctrl), ctrl)
    return act(x + mm(act(g * u, ctrl), mat(lw["ff"]["w2"], ctrl), ctrl),
               ctrl)


def hidden(c: dict, w: dict, adapter: Optional[dict], tokens, ctrl=None):
    """Final-norm hidden states (T, d) of one sequence."""
    x = common.embed(w, tokens, ctrl)
    la = adapter["layers"][0] if adapter is not None else {}

    @jax.checkpoint
    def body(x, xs):
        lw, lad = xs
        return _layer(c, ctrl, x, lw, lad), None

    x, _ = jax.lax.scan(body, x, (w["layers"][0], la))
    return act(_rms(x, w["final_norm"]["scale"], c["rms_norm_eps"]), ctrl)


def Reference(c: dict) -> common.Reference:
    return common.Reference(c, hidden)


# ---------------------------------------------------------------------------
# model FLOPs, counted from shapes
# ---------------------------------------------------------------------------

def _matmul_params(c: dict) -> tuple:
    """(base matmul weights per layer, LoRA weights per layer, head)."""
    n = dims(c)
    q, kv = n["H"] * n["hd"], n["Hkv"] * n["hd"]
    layer = n["d"] * (2 * q + 2 * kv) + 3 * n["d"] * n["ff"]
    lora = sum(a[1] * a[2] + b[1] * b[2] for a, b in lora_shapes(c).values())
    return layer, lora, n["d"] * n["V"]


def serve_step_flops(c: dict, lens, clens) -> float:
    """Model FLOPs of one mixed step's real tokens: row b holds positions
    lens[b] .. lens[b] + clens[b] - 1. Every real token pays the layers'
    matmuls (2 per weight) and attention over the keys up to itself
    (4 x keys x heads x head_dim per layer); the head runs once per row,
    for the one position that is sampled."""
    import numpy as np
    n = dims(c)
    layer, lora, head = _matmul_params(c)
    lens = np.asarray(lens, np.float64)
    clens = np.asarray(clens, np.float64)
    keys = clens * lens + clens * (clens + 1) / 2
    return float(2 * n["L"] * (layer + lora) * clens.sum()
                 + 4 * n["H"] * n["hd"] * n["L"] * keys.sum()
                 + 2 * head * (clens > 0).sum())


def train_step_flops(c: dict, rows: int, T: int) -> float:
    """Model FLOPs of one LoRA step over ``rows`` rows of T tokens: the
    forward pass and the activation gradients (2 each per weight and
    token, head included), the LoRA weight gradients, and causal attention
    forward (4 x keys x heads x head_dim) and backward (twice that).
    Recomputation is not counted."""
    n = dims(c)
    layer, lora, head = _matmul_params(c)
    per_token = 4 * (n["L"] * layer + head) + 6 * n["L"] * lora
    attn = 3 * 4 * n["H"] * n["hd"] * n["L"] * T * (T + 1) / 2
    return float(rows * (T * per_token + attn))

"""Plain float32 reference of a Jamba hybrid: Mamba-1 and attention layers.

AI21 Jamba as published (arXiv:2403.19887; hf ``JambaForCausalLM``, config
``model_type`` ``jamba``): token embedding, then per layer a pre-norm mixer
and a pre-norm gated-SiLU MLP, each added to the residual stream; a final
RMS norm and the head tied to the embedding table. Layer ``i`` mixes with
attention where ``i % attn_layer_period == attn_layer_offset`` and with
Mamba-1 elsewhere.

- Attention: causal grouped-query softmax attention with no positional
  encoding (the Mamba layers carry position), scaled by ``head_dim ** -0.5``,
  ``head_dim = hidden_size / num_attention_heads``.
- Mamba-1: ``x, z = in_proj(h)``; ``x = silu(conv(x) + conv_bias)``, a causal
  depthwise convolution over the last ``mamba_d_conv`` inputs written as
  explicit shifted sums (``conv_w[j]`` weights the input ``d_conv - 1 - j``
  steps back); ``dt_r, B, C = x_proj(x)``, each RMS-normalised with its own
  scale (``dt_layernorm``, ``b_layernorm``, ``c_layernorm``, eps
  ``rms_norm_eps``); ``dt = softplus(dt_proj(dt_r) + dt_bias)``,
  ``A = -exp(A_log)``; then, step by step over t from a zero state,
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t`` and ``y_t = C_t . h_t + D x_t``;
  ``out_proj(y * silu(z))``.
- LoRA adds ``(alpha/r) (h A) B`` to the projections it targets: ``wq``,
  ``wk``, ``wv``, ``wo`` of attention, ``mamba_in`` (in_proj) and
  ``mamba_out`` (out_proj) of Mamba.

Every matrix product runs at ``Precision.HIGHEST`` in float32 from the
stored weights; the recurrence is a ``lax.scan`` over single time steps.
Checkpoints around each layer, blocks of time steps, blocks of queries and
blocks of head rows bound the memory of the backward pass at full size; they
change no arithmetic. A checkpoint around each period of layers as well
would need less memory without a control, but under the float8 control the
v5e compiler then holds too much at once: 15.91G of the chip's 15.75G at
1 x 8192, where the per-layer checkpoints alone need 6.13 GB of arguments
and 8.85 GB of temporaries.

Only what the configurations use is modelled: one expert per layer (a dense
MLP), no projection biases, a conv bias, tied embeddings. ``dims`` refuses
anything else.

This file imports nothing of the program under test. It makes the weights
both sides use, from the seed, in the layout the program takes: a tuple of
``attn_layer_period`` positions, each stacked over the periods.
"""
from __future__ import annotations

import functools
import math
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
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "mamba_d_state": "mamba.d_state",
    "mamba_d_conv": "mamba.d_conv",
    "mamba_expand": "mamba.expand",
    "mamba_dt_rank": "mamba.dt_rank",
}
# LoRA target -> (mixer, projection)
LORA_TARGETS = {"wq": ("attn", "wq"), "wk": ("attn", "wk"),
                "wv": ("attn", "wv"), "wo": ("attn", "wo"),
                "mamba_in": ("mamba", "in_proj"),
                "mamba_out": ("mamba", "out_proj")}
TIME_BLOCK = 128      # time steps between the recurrence's checkpoints
QUERY_BLOCK = 512     # queries a block of attention scores holds
HEAD_BLOCK = 512      # positions a block of head logits holds


def dims(c: dict) -> Dict[str, int]:
    assert c["num_experts"] == 1 and c["hidden_act"] == "silu", c["name"]
    assert c["mamba_conv_bias"] and not c["mamba_proj_bias"], c["name"]
    assert c["tie_word_embeddings"], c["name"]
    d, H = c["hidden_size"], c["num_attention_heads"]
    P = c["attn_layer_period"]
    assert c["num_hidden_layers"] % P == 0, c["name"]
    return dict(L=c["num_hidden_layers"], P=P, S=c["num_hidden_layers"] // P,
                d=d, H=H, Hkv=c["num_key_value_heads"], hd=d // H,
                ff=c["intermediate_size"], V=c["vocab_size"],
                d_in=c["mamba_expand"] * d, N=c["mamba_d_state"],
                K=c["mamba_d_conv"], r=c["mamba_dt_rank"])


def kinds(c: dict) -> tuple:
    """The mixer of each position of one period."""
    return tuple("attn" if p == c["attn_layer_offset"] else "mamba"
                 for p in range(c["attn_layer_period"]))


def _divisor(T: int, most: int) -> int:
    """The largest divisor of T no larger than ``most``."""
    return max(b for b in range(1, min(T, most) + 1) if T % b == 0)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def make_weights(c: dict, key) -> dict:
    """Seeded weights (call under ``jax.jit``): bfloat16, except ``dt_bias``,
    ``A_log`` and ``D``, float32 as the program keeps them. Norm scales and
    the conv bias are drawn so that a dropped one shows."""
    n = dims(c)
    S, d, ff, V = n["S"], n["d"], n["ff"], n["V"]
    d_in, N, K, r = n["d_in"], n["N"], n["K"], n["r"]
    q, kv = n["H"] * n["hd"], n["Hkv"] * n["hd"]
    keys = iter(jax.random.split(key, 24 * n["P"] + 4))
    bf = jnp.bfloat16

    def normal(shape, std, dtype=bf):
        return (std * jax.random.normal(next(keys), shape, F32)).astype(dtype)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape, F32)
                ).astype(bf)

    def mamba():
        dt = jnp.exp(jax.random.uniform(next(keys), (S, d_in), F32,
                                        math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj": normal((S, d, 2 * d_in), d ** -0.5),
            "conv_w": normal((S, K, d_in), K ** -0.5),
            "conv_b": normal((S, d_in), 0.1),
            "x_proj": normal((S, d_in, r + 2 * N), d_in ** -0.5),
            "dt_proj": normal((S, r, d_in), r ** -0.5),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1
            "A_log": jnp.log(jnp.arange(1, N + 1, dtype=F32))
            + normal((S, d_in, N), 0.1, F32),
            "D": 1.0 + normal((S, d_in), 0.1, F32),
            "out_proj": normal((S, d_in, d), d_in ** -0.5),
            "dt_norm": scale((S, r)),
            "b_norm": scale((S, N)),
            "c_norm": scale((S, N)),
        }

    def position(kind):
        mixer = ({"wq": normal((S, d, q), d ** -0.5),
                  "wk": normal((S, d, kv), d ** -0.5),
                  "wv": normal((S, d, kv), d ** -0.5),
                  "wo": normal((S, q, d), q ** -0.5)}
                 if kind == "attn" else mamba())
        return {"norm": {"scale": scale((S, d))},
                "norm2": {"scale": scale((S, d))},
                kind: mixer,
                "ff": {"w1": normal((S, d, ff), d ** -0.5),
                       "w3": normal((S, d, ff), d ** -0.5),
                       "w2": normal((S, ff, d), ff ** -0.5)}}

    return {"embed": {"table": normal((V, d), d ** -0.5)},
            "final_norm": {"scale": scale((d,))},
            "layers": tuple(position(k) for k in kinds(c))}


def lora_shapes(c: dict) -> tuple:
    """Per position: target -> ((periods, d_in, r), (periods, r, d_out))."""
    n = dims(c)
    S, d, rank = n["S"], n["d"], c["lora"]["rank"]
    q, kv, d_in = n["H"] * n["hd"], n["Hkv"] * n["hd"], n["d_in"]
    io = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
          "mamba_in": (d, 2 * d_in), "mamba_out": (d_in, d)}
    return tuple({t: ((S, io[t][0], rank), (S, rank, io[t][1]))
                  for t in c["lora"]["targets"] if LORA_TARGETS[t][0] == kind}
                 for kind in kinds(c))


def make_adapter(c: dict, key) -> dict:
    """One float32 adapter with both factors drawn, in the program's layout
    (``common.lora_adapter``'s draw, for each position)."""
    return {"layers": tuple(
        common.lora_adapter(shapes, jax.random.fold_in(key, p))["layers"][0]
        for p, shapes in enumerate(lora_shapes(c)))}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(F32))


def _proj(c, ctrl, h, w, la, target):
    """``h W`` of the target's weight, with the adapter's delta if any."""
    sc = c["lora"]["alpha"] / c["lora"]["rank"]
    y = mm(h, mat(w, ctrl), ctrl)
    if target in la:
        y = act(y + sc * mm(mm(h, la[target]["a"], ctrl), la[target]["b"],
                            ctrl), ctrl)
    return y


def recurrence(dt, Bc, Cc, x, A, ctrl=None):
    """y_t = C_t . h_t with h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, h_0 = 0,
    one time step at a time. dt, x (T, d_in); Bc, Cc (T, N); A (d_in, N)."""
    T, d_in = x.shape
    tb = _divisor(T, TIME_BLOCK)

    def step(h, xs):
        dt_t, b_t, c_t, x_t = xs
        h = act(jnp.exp(dt_t[:, None] * A) * h
                + (dt_t * x_t)[:, None] * b_t[None, :], ctrl)
        return h, jnp.sum(h * c_t[None, :], -1)

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(step, h, xs)

    blocks = tuple(a.reshape(T // tb, tb, a.shape[-1]) for a in (dt, Bc, Cc, x))
    _, y = jax.lax.scan(block, jnp.zeros((d_in, A.shape[1]), F32), blocks)
    return y.reshape(T, d_in)


def _mamba(c, ctrl, h, lw, la):
    n = dims(c)
    T, d_in, N, K, r = h.shape[0], n["d_in"], n["N"], n["K"], n["r"]
    eps = c["rms_norm_eps"]
    xz = _proj(c, ctrl, h, lw["in_proj"], la, "mamba_in")
    x, z = xz[:, :d_in], xz[:, d_in:]
    cw = mat(lw["conv_w"], ctrl)
    xp = jnp.concatenate([jnp.zeros((K - 1, d_in), F32), x])
    conv = sum(xp[j:j + T] * cw[j] for j in range(K))
    x = act(jax.nn.silu(conv + lw["conv_b"].astype(F32)), ctrl)
    dbc = mm(x, mat(lw["x_proj"], ctrl), ctrl)
    dt_r = act(_rms(dbc[:, :r], lw["dt_norm"], eps), ctrl)
    Bc = act(_rms(dbc[:, r:r + N], lw["b_norm"], eps), ctrl)
    Cc = act(_rms(dbc[:, r + N:], lw["c_norm"], eps), ctrl)
    dt = act(jax.nn.softplus(mm(dt_r, mat(lw["dt_proj"], ctrl), ctrl)
                             + lw["dt_bias"]), ctrl)
    A = -jnp.exp(lw["A_log"])
    y = act(recurrence(dt, Bc, Cc, x, A, ctrl) + lw["D"] * x, ctrl)
    y = act(y * jax.nn.silu(z), ctrl)
    return _proj(c, ctrl, y, lw["out_proj"], la, "mamba_out")


def _attention(c, ctrl, h, lw, la):
    n = dims(c)
    T, H, Hkv, hd = h.shape[0], n["H"], n["Hkv"], n["hd"]
    q = _proj(c, ctrl, h, lw["wq"], la, "wq").reshape(T, Hkv, H // Hkv, hd)
    k = _proj(c, ctrl, h, lw["wk"], la, "wk").reshape(T, Hkv, hd)
    v = _proj(c, ctrl, h, lw["wv"], la, "wv").reshape(T, Hkv, hd)
    qb = _divisor(T, QUERY_BLOCK)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)      # (qb, Hkv, g, hd)
        s = act(mm(qi.transpose(1, 2, 0, 3), k.transpose(1, 2, 0)[:, None],
                   ctrl) * hd ** -0.5, ctrl)                  # (Hkv, g, qb, T)
        causal = jnp.arange(T)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = act(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), ctrl)
        return mm(p, v.transpose(1, 0, 2)[:, None], ctrl).transpose(2, 0, 1, 3)

    o = jax.lax.map(block, jnp.arange(T // qb))
    return _proj(c, ctrl, o.reshape(T, H * hd), lw["wo"], la, "wo")


def _layer(c, ctrl, kind, x, lw, la):
    eps = c["rms_norm_eps"]
    mixer = _attention if kind == "attn" else _mamba
    h = act(_rms(x, lw["norm"]["scale"], eps), ctrl)
    x = act(x + mixer(c, ctrl, h, lw[kind], la), ctrl)
    h2 = act(_rms(x, lw["norm2"]["scale"], eps), ctrl)
    g = act(jax.nn.silu(mm(h2, mat(lw["ff"]["w1"], ctrl), ctrl)), ctrl)
    u = mm(h2, mat(lw["ff"]["w3"], ctrl), ctrl)
    return act(x + mm(act(g * u, ctrl), mat(lw["ff"]["w2"], ctrl), ctrl),
               ctrl)


def hidden(c: dict, w: dict, adapter: Optional[dict], tokens, ctrl=None):
    """Final-norm hidden states (T, d) of one sequence."""
    x = common.embed(w, tokens, ctrl)
    ks = kinds(c)
    la = (adapter["layers"] if adapter is not None
          else tuple({} for _ in ks))

    def at(kind):
        """One layer of period ``s``: its weights are sliced inside the
        checkpoint, so the backward pass keeps the stacked arrays and not a
        copy of each slice."""
        @jax.checkpoint
        def layer(x, s, lw, la):
            take = functools.partial(jax.tree.map, lambda a: a[s])
            return _layer(c, ctrl, kind, x, take(lw), take(la))
        return layer
    layer = [at(k) for k in ks]

    def period(x, s):
        for p in range(len(ks)):
            x = layer[p](x, s, w["layers"][p], la[p])
        return x, None

    x, _ = jax.lax.scan(period, x, jnp.arange(dims(c)["S"]))
    return act(_rms(x, w["final_norm"]["scale"], c["rms_norm_eps"]), ctrl)


def tied(w: dict) -> dict:
    """The weights with the head as ``common`` reads it: the table's
    transpose (the embedding is tied)."""
    return {**w, "embed": {**w["embed"], "unembed": w["embed"]["table"].T}}


def _loss(c, ctrl, lora, w, tokens, labels):
    """Mean next-token cross entropy of one microbatch (rows, T), the tied
    head taken in blocks of positions."""
    U = mat(w["embed"]["table"], ctrl).T

    def row(tok, lab):
        x = hidden(c, w, lora, tok, ctrl)
        T = x.shape[0]
        hb = _divisor(T, HEAD_BLOCK)

        @jax.checkpoint
        def head(xs):
            xb, lb = xs
            lg = mm(xb, U, ctrl)
            return jnp.sum(jax.nn.logsumexp(lg, -1)
                           - jnp.take_along_axis(lg, lb[:, None], 1)[:, 0])
        return jnp.sum(jax.lax.map(head, (x.reshape(T // hb, hb, -1),
                                          lab.reshape(T // hb, hb))))
    return jnp.sum(jax.vmap(row)(tokens, labels)) / labels.size


class Reference(common.Reference):
    """``common.Reference``'s ``gaps`` and ``loss_and_grads`` over programs of
    the tied head: the loss takes its logits in blocks of positions, and the
    serving gaps read the table's transpose."""

    def __init__(self, c: dict):
        self.c = c
        self._gaps = {b: jax.jit(
            lambda w, ad, tok, tg, b=b: common._gaps(hidden, c, b, tied(w),
                                                     ad, tok, tg))
            for b in (None,) + common.CONTROLS}
        self._grads = {b: jax.jit(jax.value_and_grad(
            functools.partial(_loss, c, b)))
            for b in (None,) + common.CONTROLS}


# ---------------------------------------------------------------------------
# model FLOPs, counted from shapes
# ---------------------------------------------------------------------------

def _matmul_params(c: dict) -> tuple:
    """(base matmul weights of all layers, LoRA weights of all layers, head):
    attention's four projections, Mamba's in/x/dt/out projections, the MLP
    of every layer and the tied head."""
    n = dims(c)
    d, S = n["d"], n["S"]
    q, kv, d_in = n["H"] * n["hd"], n["Hkv"] * n["hd"], n["d_in"]
    mixer = {"attn": d * (2 * q + 2 * kv),
             "mamba": d * 2 * d_in + d_in * (n["r"] + 2 * n["N"])
             + n["r"] * d_in + d_in * d}
    layers = S * sum(mixer[k] + 3 * d * n["ff"] for k in kinds(c))
    lora = S * sum(a[1] * a[2] + b[1] * b[2]
                   for pos in lora_shapes(c) for a, b in pos.values())
    return layers, lora, d * n["V"]


def train_step_flops(c: dict, rows: int, T: int) -> float:
    """Model FLOPs of one LoRA step over ``rows`` rows of T tokens, counted
    as ``dense.train_step_flops`` counts them: the forward pass and the
    activation gradients (2 each per weight and token, the Mamba projections
    and the head included), the LoRA weight gradients (2 per weight and
    token), and causal attention in the attention layers, forward
    (4 x keys x heads x head_dim) and backward (twice that). The selective
    scan, the convolution, norms and gates are elementwise work and are not
    counted; nor is recomputation."""
    n = dims(c)
    layers, lora, head = _matmul_params(c)
    n_attn = n["S"] * kinds(c).count("attn")
    per_token = 4 * (layers + head) + 6 * lora
    attn = 3 * 4 * n["H"] * n["hd"] * n_attn * T * (T + 1) / 2
    return float(rows * (T * per_token + attn))


def scan_flops_per_token(c: dict) -> float:
    """Operations of the selective scan's forward pass per token, over all
    Mamba layers: per state element the decay's product and exponential,
    the increment's product, the state's multiply-add and the read-out's
    multiply-add (7); per channel dt x, the skip's multiply-add and the
    gate's product (4). A fused scan kernel's roofline reads this."""
    n = dims(c)
    n_mamba = n["S"] * kinds(c).count("mamba")
    return float(n_mamba * n["d_in"] * (7 * n["N"] + 4))


def scan_bytes_per_token(c: dict, itemsize: int = 4) -> float:
    """Least HBM traffic of that forward pass per token, over all Mamba
    layers: dt, x and z read and y written per channel, B and C read per
    state, the state itself kept on chip."""
    n = dims(c)
    n_mamba = n["S"] * kinds(c).count("mamba")
    return float(n_mamba * itemsize * (4 * n["d_in"] + 2 * n["N"]))

"""What every plain reference shares: float32 products at
``Precision.HIGHEST``, the controls, the logit-gap reading over a vocabulary
taken in blocks, the loss and its LoRA gradients, and AdamW.

A control is the reference one precision step below what the configuration
states, named by ``ctrl``: ``"bf16"`` keeps activations and the KV in
bfloat16 (every product takes bfloat16 inputs and gives a bfloat16 result,
and what a layer stores is rounded to bfloat16), ``"fp8"`` rounds the stored
base weights to float8 e4m3. ``None`` is the reference itself.

An architecture's module gives ``hidden(c, w, adapter, tokens, ctrl)``, the
final-norm hidden states of one sequence; ``Reference`` builds the rest on
it. Nothing here imports the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16
CONTROLS = ("bf16", "fp8")


def lowp(w, amax):
    """float8 e4m3 with one scale per tensor, back in float32."""
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (w.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mat(w, ctrl=None):
    """A stored weight matrix in float32; under the float8 control, rounded
    to float8 first."""
    if ctrl == "fp8":
        return lowp(w, jnp.max(jnp.abs(w)).astype(F32))
    return w.astype(F32)


def act(x, ctrl=None):
    """An activation as stored: under the bfloat16 control, rounded to
    bfloat16."""
    return x.astype(BF16).astype(F32) if ctrl == "bf16" else x


def mm(a, b, ctrl=None):
    """A matrix product in float32; under the bfloat16 control, of bfloat16
    inputs with a bfloat16 result (accumulated in float32)."""
    if ctrl == "bf16":
        return act(jnp.matmul(a.astype(BF16), b.astype(BF16),
                              preferred_element_type=F32), ctrl)
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def embed(w, tokens, ctrl=None):
    table = w["embed"]["table"]
    x = table[tokens].astype(F32)
    if ctrl == "fp8":
        return lowp(x, jnp.max(jnp.abs(table)).astype(F32))
    return x


def _vocab_blocks(V: int) -> int:
    for nb in (16, 8, 4, 2):
        if V % nb == 0:
            return nb
    return 1


def _gaps(hidden, c, ctrl, w, adapter, tokens, targets):
    """Per position: the reference's best logit less its logit of the
    token there, ``targets`` (the served one) or, under a control, the one
    the control puts first."""
    x = hidden(c, w, adapter, tokens)
    xc = hidden(c, w, adapter, tokens, ctrl) if ctrl else None
    U = w["embed"]["unembed"]
    d, V = U.shape
    nb = _vocab_blocks(V)
    Ub = U.reshape(d, nb, V // nb).transpose(1, 0, 2)
    amax = jnp.max(jnp.abs(U)).astype(F32)
    T = tokens.shape[0]
    init = (jnp.full((T,), -jnp.inf), jnp.zeros((T,)),
            jnp.full((T,), -jnp.inf))

    def blk(carry, xs):
        best, at, cbest = carry
        i, ub = xs
        lg = mm(x, ub.astype(F32))                            # (T, Vb)
        best = jnp.maximum(best, lg.max(-1))
        if ctrl is None:
            lo = i * ub.shape[1]
            inb = (targets >= lo) & (targets < lo + ub.shape[1])
            got = jnp.take_along_axis(
                lg, jnp.clip(targets - lo, 0, ub.shape[1] - 1)[:, None],
                axis=1)[:, 0]
            return (best, jnp.where(inb, got, at), cbest), None
        lc = mm(xc, lowp(ub, amax) if ctrl == "fp8" else ub.astype(F32),
                ctrl)
        arg = jnp.argmax(lc, -1)
        m = jnp.take_along_axis(lc, arg[:, None], axis=1)[:, 0]
        ref_at = jnp.take_along_axis(lg, arg[:, None], axis=1)[:, 0]
        return (best, jnp.where(m > cbest, ref_at, at),
                jnp.maximum(cbest, m)), None

    (best, at, _), _ = jax.lax.scan(blk, init, (jnp.arange(nb), Ub))
    return best - at


def _loss(hidden, c, ctrl, lora, w, tokens, labels):
    """Mean next-token cross entropy of one microbatch (rows, T)."""
    def row(tok, lab):
        x = hidden(c, w, lora, tok, ctrl)

        @jax.checkpoint
        def head(x):
            lg = mm(x, mat(w["embed"]["unembed"], ctrl), ctrl)
            lse = jax.nn.logsumexp(lg, -1)
            return jnp.sum(lse - jnp.take_along_axis(lg, lab[:, None],
                                                     1)[:, 0])
        return head(x)
    return jnp.sum(jax.vmap(row)(tokens, labels)) / labels.size


class Reference:
    """The reference for one configuration, its programs compiled once.

    ``gaps(w, adapter, tokens, targets, ctrl)``: per position t, how far
    the reference's logit of ``targets[t]`` lies below its best logit at t;
    under a control, the same for the token the control puts first.
    ``tokens`` (T,) is one sequence.

    ``loss_and_grads(lora, w, tokens, labels, micro_rows, ctrl)``: mean loss
    and LoRA gradients over all rows, one microbatch of ``micro_rows`` rows
    at a time (the mean of the microbatch means)."""

    def __init__(self, c: dict, hidden):
        self.c = c
        self._gaps = {b: jax.jit(functools.partial(_gaps, hidden, c, b))
                      for b in (None,) + CONTROLS}
        self._grads = {b: jax.jit(jax.value_and_grad(
            functools.partial(_loss, hidden, c, b)))
            for b in (None,) + CONTROLS}

    def gaps(self, w, adapter, tokens, targets, ctrl=None):
        return self._gaps[ctrl](w, adapter, tokens, targets)

    def loss_and_grads(self, lora, w, tokens, labels, micro_rows, ctrl=None):
        fn = self._grads[ctrl]
        n = tokens.shape[0] // micro_rows
        loss, grads = 0.0, None
        for i in range(n):
            sl = slice(i * micro_rows, (i + 1) * micro_rows)
            l, g = fn(lora, w, tokens[sl], labels[sl])
            loss = loss + l
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return loss / n, jax.tree.map(lambda g: g / n, grads)


def adamw_step(lora, grads, m, v, step: int, opt: dict):
    """One AdamW step: clip by the global gradient norm, bias-corrected
    moments, no weight decay. Returns (lora, m, v, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = opt.get("grad_clip")
    if clip is not None:
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12)),
            grads)
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    lora = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        lora, m, v)
    return lora, m, v, grads


def lora_adapter(shapes: dict, key) -> dict:
    """One float32 LoRA adapter with both factors drawn, as a trained
    adapter has them (``B = 0`` would make every adapter the base model).
    ``shapes``: target -> ((L, d_in, r), (L, r, d_out))."""
    tree = {}
    for i, (t, (sa, sb)) in enumerate(sorted(shapes.items())):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        tree[t] = {"a": sa[1] ** -0.5 * jax.random.normal(ka, sa, F32),
                   "b": 0.5 * sb[1] ** -0.5 * jax.random.normal(kb, sb, F32)}
    return {"layers": (tree,)}

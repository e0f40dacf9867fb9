"""Jittable step functions: train (LoRA fine-tune), prefill, decode.

These are the functions the multi-pod dry-run lowers and the trainer /
serving engine execute. Gradient accumulation runs as a microbatch scan
inside the step (the PipeLayer-style batch pipeline the paper inherits);
only the LoRA accumulator is carried — base weights never have gradients.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models.transformer import ExecConfig
from repro.optim import adamw

Array = jax.Array


@dataclass(frozen=True)
class TrainHParams:
    microbatches: int = 1
    adamw: adamw.AdamWConfig = adamw.AdamWConfig()
    full_finetune: bool = False   # paper mode is PEFT (LoRA-only)


def _split_micro(batch: Dict[str, Array], n: int) -> Dict[str, Array]:
    return jax.tree.map(lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]),
                        batch)


def make_loss_fn(cfg: ModelConfig, ec: ExecConfig):
    def loss_fn(lora, params, micro, rng):
        inputs = ({"tokens": micro["tokens"]} if "tokens" in micro
                  else {"embeds": micro["embeds"]})
        logits, _, aux = tfm.forward(cfg, params, inputs, lora=lora,
                                     mode="train", exec_cfg=ec, rng=rng)
        with jax.named_scope(obs.LOSS):
            loss, metrics = tfm.lm_loss(cfg, logits, micro["labels"],
                                        micro.get("mask"))
        return loss, {**metrics, "lb_loss": aux["lb_loss"]}
    return loss_fn


def make_train_step(cfg: ModelConfig, ec: ExecConfig, hp: TrainHParams
                    ) -> Callable:
    """(params, lora, opt_state, batch, rng) ->
    (lora, opt_state, metrics). ``batch``: tokens/embeds (B, T), labels."""
    loss_fn = make_loss_fn(cfg, ec)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, lora, opt_state, batch, rng):
        n = hp.microbatches
        if n > 1:
            micro = _split_micro(batch, n)

            def mb_body(carry, xs):
                gacc, lacc = carry
                mb, i = xs
                (loss, mx), g = grad_fn(lora, params, mb,
                                        jax.random.fold_in(rng, i))
                gacc = jax.tree.map(lambda a, b: a + b, gacc, g)
                return (gacc, lacc + loss), None

            g0 = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), lora)
            (gsum, lsum), _ = jax.lax.scan(
                mb_body, (g0, jnp.zeros((), jnp.float32)),
                (micro, jnp.arange(n)))
            grads = jax.tree.map(lambda g: g / n, gsum)
            loss = lsum / n
            metrics: Dict[str, Array] = {}
        else:
            (loss, metrics), grads = grad_fn(lora, params, batch, rng)
        with jax.named_scope(obs.OPTIMIZER):
            new_lora, new_opt, om = adamw.apply_updates(hp.adamw, lora, grads,
                                                        opt_state)
        return new_lora, new_opt, {"loss": loss, **metrics, **om}

    return step


def make_prefill_step(cfg: ModelConfig, ec: ExecConfig,
                      cache_len: Optional[int] = None) -> Callable:
    """(params, lora, inputs, positions) -> (last_logits, cache)."""
    def step(params, lora, inputs, positions=None):
        logits, cache, _ = tfm.forward(
            cfg, params, inputs, lora=lora, positions=positions,
            mode="prefill", prefill_cache_len=cache_len, exec_cfg=ec)
        return logits[:, -1, :], cache
    return step


def make_decode_step(cfg: ModelConfig, ec: ExecConfig) -> Callable:
    """(params, lora, cache, inputs[, adapter_idx]) -> (logits (B,V), cache)."""
    def step(params, lora, cache, inputs, adapter_idx=None):
        logits, new_cache, _ = tfm.forward(
            cfg, params, inputs, lora=lora, cache=cache, mode="decode",
            exec_cfg=ec, adapter_idx=adapter_idx)
        return logits[:, -1, :], new_cache
    return step

"""Config-driven decoder: embeds -> scan over period-blocks -> norm -> head.

One ``apply_position`` handles any block kind (attn / mamba / rwkv) plus its
FF (dense or MoE); ``lax.scan`` runs over stacked scan-periods so the HLO
contains each distinct layer shape exactly once (essential for compiling
398B-param configs in the dry-run). LoRA adapters and decode caches mirror
the same layout and are scanned alongside.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import hetero
from repro.core.lora import scan_period
from repro.core.noise import NoiseConfig
from repro.models import attention, layers, moe, rwkv, ssm

Array = jax.Array


@dataclass(frozen=True)
class ExecConfig:
    """Runtime execution knobs (orthogonal to the model config)."""

    attn_impl: str = "auto"         # auto | ref | blocked | banded | pallas
    block_q: int = 2048
    block_kv: int = 512
    remat: bool = False
    scan_layers: bool = True
    capacity_factor: Optional[float] = None
    moe_group_size: Optional[int] = None
    moe_dispatch: str = "capacity"  # capacity (training) | dropless (serving)
    noise: NoiseConfig = field(default_factory=NoiseConfig)
    act_dtype: Any = jnp.float32
    rwkv_impl: str = "auto"
    sharder: Optional[Callable[[Array, str], Array]] = None
    moe_parallel: int = 1           # expert slots >= this (mesh model width)

    def shard(self, x: Array, name: str) -> Array:
        return self.sharder(x, name) if self.sharder is not None else x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_position(cfg: ModelConfig, pos: int, key: Array, dtype,
                   moe_parallel: int) -> Dict:
    kind = cfg.block_kind(pos)
    ks = jax.random.split(key, 4)
    if kind == "rwkv":
        return rwkv.init_rwkv(cfg, ks[0], dtype)
    entry: Dict[str, Any] = {"norm": layers.init_norm(cfg, dtype),
                             "norm2": layers.init_norm(cfg, dtype)}
    if kind == "attn":
        entry["attn"] = attention.init_attn(cfg, ks[0], dtype)
    elif kind == "mamba":
        entry["mamba"] = ssm.init_mamba(cfg, ks[0], dtype)
    if cfg.is_moe_layer(pos):
        entry["ff"] = moe.init_moe(cfg, ks[1], dtype, moe_parallel)
    else:
        entry["ff"] = layers.init_mlp(cfg, ks[1], dtype)
    return entry


def init_params(cfg: ModelConfig, key: Array, dtype=jnp.float32,
                moe_parallel: int = 1) -> Dict:
    p = scan_period(cfg)
    n_sp = cfg.n_layers // p
    k_emb, k_layers = jax.random.split(key)
    pos_keys = jax.random.split(k_layers, p)
    layer_trees = []
    for pos in range(p):
        per_period = jax.random.split(pos_keys[pos], n_sp)
        stacked = jax.vmap(
            lambda k: _init_position(cfg, pos, k, dtype, moe_parallel)
        )(per_period)
        layer_trees.append(stacked)
    return {
        "embed": layers.init_embed(cfg, k_emb, dtype),
        "final_norm": layers.init_norm(cfg, dtype),
        "layers": tuple(layer_trees),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _apply_position(cfg: ModelConfig, ec: ExecConfig, pos: int, x: Array,
                    pparams, plora, pcache, positions: Array, mode: str,
                    prefill_cache_len: Optional[int], rng, adapter_idx,
                    paged=None, chunk_lens=None
                    ) -> Tuple[Array, Any, Dict[str, Array]]:
    kind = cfg.block_kind(pos)
    aux: Dict[str, Array] = {}
    noise = ec.noise if (ec.noise.enabled and mode == "train") else None

    if kind == "rwkv":
        x, newc = rwkv.apply_rwkv_block(
            cfg, pparams, x, cache=pcache, lora=plora, adapter_idx=adapter_idx,
            noise=noise, rng=rng, impl=ec.rwkv_impl, sharder=ec.sharder,
            chunk_lens=chunk_lens)
        return ec.shard(x, "act"), newc, aux

    with jax.named_scope(obs.ATTN if kind == "attn" else obs.RECURRENT):
        h = ec.shard(layers.apply_norm(cfg, pparams["norm"], x), "act")
        if kind == "attn":
            delta, newc = attention.apply_attention_block(
                cfg, pparams["attn"], h, positions,
                kind=cfg.attn_kind(pos), mode=mode, cache=pcache,
                prefill_cache_len=prefill_cache_len, lora=plora,
                adapter_idx=adapter_idx, noise=noise, rng=rng,
                impl=ec.attn_impl, block_q=ec.block_q, block_kv=ec.block_kv,
                sharder=ec.sharder, paged=paged,
                chunk_lens=chunk_lens if mode == "prefill" else None)
        elif kind == "mamba":
            # the scan has a cross-shard sequence dependency
            h = ec.shard(h, "act_gathered")
            delta, newc = ssm.apply_mamba_block(
                cfg, pparams["mamba"], h, cache=pcache, lora=plora,
                adapter_idx=adapter_idx, noise=noise, rng=rng,
                sharder=ec.sharder, chunk_lens=chunk_lens)
            delta = ec.shard(delta, "act")
        else:
            raise KeyError(kind)
        x = ec.shard(x + delta, "act")

    with jax.named_scope(obs.MLP):
        h2 = ec.shard(layers.apply_norm(cfg, pparams["norm2"], x), "act")
        if cfg.is_moe_layer(pos):
            token_mask = None
            if chunk_lens is not None:
                token_mask = (jnp.arange(x.shape[1])[None, :]
                              < chunk_lens[:, None])
            ff_out, aux = moe.apply_moe(
                cfg, pparams["ff"], h2, noise=noise, rng=rng,
                capacity_factor=ec.capacity_factor, sharder=ec.sharder,
                group_size=ec.moe_group_size, token_mask=token_mask,
                dispatch=ec.moe_dispatch)
        else:
            ff_out = layers.apply_mlp(cfg, pparams["ff"], h2, noise=noise,
                                      rng=rng, sharder=ec.sharder)
        x = ec.shard(x + ff_out, "act")
    return x, newc, aux


def forward(cfg: ModelConfig, params: Dict, inputs: Dict[str, Array], *,
            lora: Optional[Dict] = None, cache: Optional[Dict] = None,
            positions: Optional[Array] = None, mode: str = "train",
            prefill_cache_len: Optional[int] = None,
            exec_cfg: ExecConfig = ExecConfig(), rng: Optional[Array] = None,
            adapter_idx: Optional[Array] = None,
            paged: Optional[Dict[str, Array]] = None,
            chunk_lens: Optional[Array] = None,
            ) -> Tuple[Array, Optional[Dict], Dict[str, Array]]:
    """Returns (logits (B,T,V), new_cache, aux).

    inputs: {"tokens": (B,T) int32} or {"embeds": (B,T,d)} (stub frontend).
    positions: (B,T) global token positions (defaults to arange / cache len).
    paged: block-table state for the paged decode path (see
    ``attention.apply_attention_block``); chunk_lens (B,) marks ragged
    chunks — rows are valid for their first chunk_lens[b] tokens only.
    aux carries "lb_loss" (summed MoE load-balance loss) and
    "moe_dropped_tokens" (capacity-dropped (token, expert) assignments
    summed over layers — identically 0 when exec_cfg.moe_dispatch is
    "dropless", the mode the serving engines force).
    """
    ec = exec_cfg
    P = scan_period(cfg)
    n_sp = cfg.n_layers // P

    with jax.named_scope(obs.EMBED):
        if "tokens" in inputs:
            x = layers.embed_tokens(cfg, params["embed"], inputs["tokens"],
                                    ec.act_dtype)
        else:
            x = inputs["embeds"].astype(ec.act_dtype)
    B, T = x.shape[0], x.shape[1]

    if positions is None:
        if mode == "decode" and cache is not None:
            from repro.models.kvcache import cache_len
            cur = cache_len(cache)
            if cur is None:
                positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
            else:
                positions = cur[:, None] + jnp.arange(T)[None]
        else:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    positions = ec.shard(positions, "pos")
    x = ec.shard(x, "act")

    lora_layers = lora["layers"] if lora is not None else tuple({} for _ in range(P))
    cache_layers = cache["layers"] if cache is not None else tuple(None for _ in range(P))

    apply_position = _apply_position
    if ec.remat and P > 1:
        # a period of several layers: keep one layer's activations live at a
        # time in the backward pass, not the whole period's. This nests in
        # the period's checkpoint below, so a layer's forward runs three
        # times; dropping the period's checkpoint saves one run but keeps
        # every layer's input: jamba2-3b at 1 x 8192 on a v5e then needs
        # 6.27 GB of arguments and 10.32 GB of temporaries, over the chip's
        # 15.75 GB, where the nesting needs 8.04 GB of temporaries
        apply_position = jax.checkpoint(
            _apply_position, static_argnums=(0, 1, 2, 8, 9),
            policy=jax.checkpoint_policies.nothing_saveable)

    def period_fn(x, period_idx, pparams_t, plora_t, pcache_t, rng):
        new_caches = []
        all_aux = []
        for pos in range(P):
            prng = (jax.random.fold_in(rng, period_idx * P + pos)
                    if rng is not None else None)
            pc = pcache_t[pos] if pcache_t is not None else None
            if pc is None and mode == "prefill" and cfg.block_kind(pos) != "attn":
                # mamba/rwkv must emit their state from prefill: start at zero
                from repro.models.kvcache import position_cache_spec
                spec = position_cache_spec(cfg, pos, B, 1, ec.act_dtype)
                pc = {k: jnp.zeros(s, d) for k, (s, d) in spec.items()}
            x, newc, aux = apply_position(
                cfg, ec, pos, x, pparams_t[pos], plora_t[pos], pc,
                positions, mode, prefill_cache_len, prng, adapter_idx,
                paged, chunk_lens)
            new_caches.append(newc)
            all_aux.append(aux)
        lb = sum([a.get("lb_loss", jnp.zeros((), jnp.float32)) for a in all_aux],
                 jnp.zeros((), jnp.float32))
        drop = sum([a.get("dropped_tokens", jnp.zeros((), jnp.float32))
                    for a in all_aux], jnp.zeros((), jnp.float32))
        return x, tuple(new_caches), lb, drop

    if ec.scan_layers and n_sp > 1:
        def scan_body(carry, xs):
            x, lb_acc, drop_acc = carry
            period_idx, pparams_t, plora_t, pcache_t = xs
            x, newc, lb, drop = period_fn(x, period_idx, pparams_t, plora_t,
                                          pcache_t, rng)
            return (x, lb_acc + lb, drop_acc + drop), newc

        if ec.remat:
            scan_body = jax.checkpoint(
                scan_body, policy=jax.checkpoint_policies.nothing_saveable)
        xs = (jnp.arange(n_sp), params["layers"], lora_layers,
              cache_layers if cache is not None else None)
        (x, lb_total, drop_total), new_cache_layers = jax.lax.scan(
            scan_body,
            (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), xs)
    else:
        lb_total = jnp.zeros((), jnp.float32)
        drop_total = jnp.zeros((), jnp.float32)
        new_cache_layers = []
        # unrolled: slice each period manually
        for sp in range(n_sp):
            pparams_t = jax.tree.map(lambda a: a[sp], params["layers"])
            plora_t = jax.tree.map(lambda a: a[sp], lora_layers)
            pcache_t = (jax.tree.map(lambda a: a[sp], cache_layers)
                        if cache is not None else None)
            x, newc, lb, drop = period_fn(x, sp, pparams_t, plora_t,
                                          pcache_t, rng)
            lb_total = lb_total + lb
            drop_total = drop_total + drop
            new_cache_layers.append(newc)
        if cache is not None or mode == "prefill":
            new_cache_layers = jax.tree.map(
                lambda *xs: jnp.stack(xs), *new_cache_layers)

    with jax.named_scope(obs.HEAD):
        x = layers.apply_norm(cfg, params["final_norm"], x)
        x = ec.shard(x, "act_gathered")
        logits = layers.unembed(cfg, params["embed"], x)
        logits = ec.shard(logits, "logits")

    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"layers": tuple(new_cache_layers)}
    aux = {"lb_loss": lb_total, "moe_dropped_tokens": drop_total}
    return logits, new_cache, aux


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(cfg: ModelConfig, logits: Array, labels: Array,
            mask: Optional[Array] = None) -> Tuple[Array, Dict[str, Array]]:
    """Token-mean cross entropy over (possibly vocab-sharded) logits.

    The label logit is extracted with a one-hot multiply-reduce rather than
    take_along_axis: gathers over a TP-sharded vocab axis make GSPMD
    replicate the whole logits tensor (53 GiB/device for llama4-scout at
    train_4k); multiply-reduce stays sharded and lowers to one tiny psum."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    onehot = (labels[..., None] == jnp.arange(lf.shape[-1])[None, None, :])
    ll = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    nll = lse - ll
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    mask = mask.astype(jnp.float32)
    tot = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / tot
    return loss, {"nll_sum": jnp.sum(nll * mask), "tokens": tot}

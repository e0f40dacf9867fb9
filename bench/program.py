"""The bridge to the program under test: its model config built from a
configuration file, and a check that the benchmark's weights have the layout
the program's own initialiser would give them."""
from __future__ import annotations

import dataclasses
import sys
from types import ModuleType

from bench.spec import CHECKOUT

if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(CHECKOUT / "src"))


def _set(obj, dotted: str, value):
    head, _, rest = dotted.partition(".")
    if rest:
        return dataclasses.replace(obj, **{head: _set(getattr(obj, head),
                                                      rest, value)})
    return dataclasses.replace(obj, **{head: value})


def model_config(c: dict, ref: ModuleType):
    """The program's config for configuration ``c``: the program's own
    architecture entry with every number the file states put in."""
    from repro.configs import get_config
    from repro.configs.base import LoRAConfig
    cfg = get_config(c["program_arch"])
    for key, field in ref.PROGRAM_FIELDS.items():
        cfg = _set(cfg, field, c[key])
    lo = c["lora"]
    cfg = dataclasses.replace(cfg, name=c["name"], lora=LoRAConfig(
        rank=lo["rank"], alpha=float(lo["alpha"]),
        targets=tuple(lo["targets"])))
    return cfg.validate()


def check_layout(cfg, weights, adapter) -> None:
    """Raise if the weights or the adapter differ in structure, shape or
    dtype from what the program's initialisers make (bfloat16 weights,
    float32 adapters)."""
    import jax
    import jax.numpy as jnp
    from repro.core.lora import init_lora_params
    from repro.models.transformer import init_params
    key = jax.random.PRNGKey(0)
    for what, want, got in (
            ("weights", jax.eval_shape(
                lambda k: init_params(cfg, k, jnp.bfloat16), key), weights),
            ("adapter", jax.eval_shape(
                lambda k: init_lora_params(cfg, k), key), adapter)):
        w = jax.tree_util.tree_flatten_with_path(want)[0]
        g = jax.tree_util.tree_flatten_with_path(jax.eval_shape(lambda: got))[0]
        ws = {jax.tree_util.keystr(p): (l.shape, l.dtype) for p, l in w}
        gs = {jax.tree_util.keystr(p): (l.shape, l.dtype) for p, l in g}
        if ws != gs:
            diff = sorted(k for k in ws.keys() | gs.keys()
                          if ws.get(k) != gs.get(k))
            raise ValueError(f"{what} layout differs from the program's at "
                             f"{diff[:6]}: program {[ws.get(k) for k in diff[:6]]}"
                             f", benchmark {[gs.get(k) for k in diff[:6]]}")

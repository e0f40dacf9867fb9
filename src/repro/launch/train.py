"""Training launcher.

CPU-scale run (this container):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 100 --batch 8 --seq 128

Production pods: the same entrypoint builds the (data, model) mesh from
``jax.devices()``, shards params via ``repro.dist.sharding`` and runs the
identical Trainer (the dry-run proves the lowering for the full configs).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.configs.base import QuantConfig
from repro.core import quant as quant_lib
from repro.core.noise import NoiseConfig
from repro.data.pipeline import make_dataset
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import ExecConfig, init_params
from repro.optim.adamw import AdamWConfig, warmup_cosine
from repro.train.steps import TrainHParams
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--quant", default="bf16", help="bf16 | M8F8 | M8F4 | ...")
    ap.add_argument("--noise-sigma", type=float, default=0.0,
                    help="noise-aware fine-tuning sigma_rel")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data", default=None, help="memmap token file")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.quant != "bf16":
        import re
        m = re.fullmatch(r"M(\d+)F(\d+)", args.quant)
        qc = QuantConfig(mha_bits=int(m.group(1)), ff_bits=int(m.group(2)))
        params = quant_lib.quantize_params(params, qc, min_size=1)
        print(f"quantized base ({qc.tag})")

    noise = NoiseConfig(enabled=args.noise_sigma > 0,
                        sigma_rel=args.noise_sigma)
    ec = ExecConfig(noise=noise, capacity_factor=2.0)
    hp = TrainHParams(
        microbatches=args.microbatches,
        adamw=AdamWConfig(lr=args.lr,
                          schedule=warmup_cosine(args.steps // 10, args.steps)))
    tc = TrainerConfig(seq_len=args.seq, global_batch=args.batch,
                       steps=args.steps, ckpt_dir=args.ckpt_dir,
                       hparams=hp, seed=args.seed)
    ds = make_dataset(cfg.vocab_size, args.seed, args.data)
    tr = Trainer(cfg, tc, ds, exec_cfg=ec, params=params)
    tr.maybe_restore()
    log = tr.run_with_restarts()
    print(f"done: {len(log)} steps, loss {log[0]['loss']:.4f} -> "
          f"{log[-1]['loss']:.4f}")
    return log


if __name__ == "__main__":
    main()

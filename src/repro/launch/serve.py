"""Serving launcher: batched multi-adapter LoRA inference.

  # production path: paged arena, chunked prefill, CoW prefix sharing
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 8 --adapters 2 --max-new 16

  # shared-prefix traffic (few prompt families -> high prefix-cache hits)
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 16 --prompt-families 4

  # speculative decoding: n-gram or quantized self-draft drafter
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --spec-decode --draft ngram --spec-k 4

  # tensor-parallel paged decode over N local devices
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --tp 4

  # dense oracle (equivalence baseline only)
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --engine dense
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core import lora as lora_lib
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import init_params
from repro.serve.api import ParallelConfig, Request, make_engine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--adapters", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=("paged", "dense"), default="paged",
                    help="paged = production engine; dense = oracle baseline")
    ap.add_argument("--paged", action="store_true",
                    help="deprecated (paged is now the default engine)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool size (default: half the dense arena)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable CoW prefix sharing in the paged engine")
    ap.add_argument("--prompt-families", type=int, default=0,
                    help="> 0: draw prompts from N shared-prefix families")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding (paged engine only): draft "
                         "k tokens per slot, verify in one mixed step, "
                         "roll back rejected KV")
    ap.add_argument("--draft", choices=("ngram", "selfdraft"),
                    default="ngram",
                    help="drafter: model-free n-gram lookup, or the target "
                         "model with quantize_params-compressed weights")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per slot per tick")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor parallelism over the first N local devices "
                         "(paged engine only)")
    ap.add_argument("--moe-dispatch", choices=("dropless", "capacity"),
                    default="dropless",
                    help="MoE routing for the paged engine: dropless "
                         "(default; tokens never drop, output invariant to "
                         "prefill chunking) or capacity (training-style "
                         "buckets, baseline comparison only)")
    ap.add_argument("--prefix-cache-path", default=None,
                    help="persist/restore the prefix index at this .npz path")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    adapters = [lora_lib.init_lora_params(cfg, jax.random.fold_in(key, i + 1))
                for i in range(args.adapters)]
    spec = None
    if args.spec_decode:
        if args.engine != "paged":
            raise SystemExit("--spec-decode requires --engine paged")
        from repro.serve.spec import SpecConfig
        spec = SpecConfig(k=args.spec_k, drafter=args.draft)
    if args.engine == "paged":
        eng = make_engine(cfg, params, adapters, mode="paged",
                          max_slots=args.max_batch,
                          max_len=args.max_len,
                          page_size=args.page_size,
                          num_pages=args.num_pages,
                          prefill_chunk=args.prefill_chunk,
                          enable_prefix_cache=not args.no_prefix_cache,
                          spec=spec,
                          parallel=ParallelConfig(tp=args.tp),
                          prefix_cache_path=args.prefix_cache_path,
                          moe_dispatch=args.moe_dispatch,
                          seed=args.seed)
    else:
        if args.tp > 1:
            raise SystemExit("--tp requires --engine paged")
        if args.moe_dispatch != "dropless":
            raise SystemExit("--moe-dispatch capacity requires --engine "
                             "paged (the dense oracle always routes "
                             "dropless)")
        eng = make_engine(cfg, params, adapters, mode="dense",
                          max_batch=args.max_batch, max_len=args.max_len,
                          seed=args.seed)
    rng = np.random.default_rng(args.seed)
    fams = [rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
            for _ in range(args.prompt_families)]
    t0 = time.time()
    for i in range(args.requests):
        if fams:
            head = fams[i % len(fams)]
            tail = rng.integers(0, cfg.vocab_size,
                                int(rng.integers(2, 8))).astype(np.int32)
            prompt = np.concatenate([head, tail])[:args.max_len - args.max_new
                                                  - 1]
        else:
            plen = int(rng.integers(4, 16))
            prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        eng.submit(Request(uid=i, prompt=prompt, max_new_tokens=args.max_new,
                           adapter_id=i % max(args.adapters, 1),
                           temperature=args.temperature))
    done = eng.drain()
    dt = time.time() - t0
    total_toks = sum(c.n_tokens for c in done.values())
    print(f"[{args.engine}] served {len(done)} requests / {total_toks} tokens "
          f"in {dt:.2f}s ({total_toks / dt:.1f} tok/s, {args.adapters} "
          f"adapters hot)")
    stats = eng.stats()
    print(f"  stats: {stats.as_dict()}")
    if stats.parallel.tp > 1:
        par = stats.parallel
        print(f"  tp={par.tp} over {list(par.devices)}: "
              f"{par.param_bytes_per_device} param bytes/device, "
              f"{par.kv_bytes_per_device} KV bytes/device")
    if stats.moe.enabled:
        print(f"  moe[{stats.moe.dispatch}]: "
              f"dropped_tokens={stats.moe.dropped_tokens}")
    if args.spec_decode:
        sp = stats.spec
        print(f"  spec[{args.draft} k={args.spec_k}]: "
              f"accept_rate={sp.accept_rate:.2f} "
              f"drafted={sp.drafted_tokens} accepted={sp.accepted_tokens} "
              f"rolled_back={sp.rolled_back_tokens} "
              f"(disabled: {sp.disabled_reason or 'no'})")
    for uid in sorted(done)[:4]:
        print(f"  req {uid} adapter={done[uid].adapter_id} "
              f"[{done[uid].finish_reason}]: {done[uid].tokens[:10]}")
    return done


if __name__ == "__main__":
    main()

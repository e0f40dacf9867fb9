"""Smoke run of the serving engine and the LoRA trainer on a TPU.

    python chip_smoke.py            # one chip: serve, reference check, train
    python chip_smoke.py --tp 4     # four chips: tensor-parallel serving only

The model is llama3.2-1b at its published widths (16 layers, d_model 2048,
GQA 32/8, vocab 128256) with random weights drawn from ``--seed``. Every
phase runs through the entry points a user calls (``make_engine``,
``repro.launch.train.main``) in this one process, and every phase checks
its own output; any failed check exits non-zero. Timings, compile counts
and memory are printed for the record only; they are not benchmark
numbers. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU the script exits non-zero before it builds a model.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

# the reference check runs the same forward on the host CPU, in this
# process: keep the CPU backend beside the accelerator when the platform
# list is pinned
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "llama3.2-1b"
N_ADAPTERS = 4
MAX_SLOTS, MAX_LEN, PAGE_SIZE, PREFILL_CHUNK = 8, 1024, 16, 256
MAX_NEW = 32
REF_TOKENS = 128
# Chip logits against the host CPU's float32 forward: worst position's
# relative L2 error, max_t |chip_t - cpu_t| / |cpu_t| over the logit
# vectors. At default precision the TPU runs each float32 matmul as one
# bfloat16 pass (8-bit significand, unit roundoff 2^-9 ~ 2e-3). Sixteen
# layers of such rounding leave the logits about 1e-2 apart (1.05e-2 on a
# v5e at seed 0); 5e-2 keeps headroom above that, while a model missing
# one of its sixteen layers is off by about ten times the bound (0.42 on
# the same v5e run). The check proves the last claim on every run: a CPU
# reference with one layer zeroed must fail the bound.
LOGIT_RTOL = 5e-2
CONTROL_LAYER = 8


def check(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def require_tpu():
    """The device check; runs before any model is built."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def make_requests(vocab: int, seed: int):
    """8 prompts with lengths spread over 32..512, then 4 that share one
    256-token prefix (same adapter, so the prefix cache can serve them)."""
    from repro.serve.api import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(np.linspace(32, 512, 8).round().astype(int)):
        reqs.append(Request(uid=i, prompt=rng.integers(0, vocab, n, np.int32),
                            max_new_tokens=MAX_NEW, adapter_id=i % N_ADAPTERS))
    prefix = rng.integers(0, vocab, 256, np.int32)
    for j in range(4):
        tail = rng.integers(0, vocab, int(rng.integers(8, 64)), np.int32)
        reqs.append(Request(uid=8 + j, prompt=np.concatenate([prefix, tail]),
                            max_new_tokens=MAX_NEW, adapter_id=1))
    return reqs


def build_model(cfg, seed: int):
    from repro.core import lora as lora_lib
    from repro.models.transformer import init_params
    key = jax.random.PRNGKey(seed)
    # one compiled program: eager init compiles each of its ops on its own
    params = jax.jit(init_params, static_argnums=0)(cfg, key)
    adapters = [lora_lib.init_lora_params(cfg, jax.random.fold_in(key, i + 1))
                for i in range(N_ADAPTERS)]
    return params, adapters


def serve(eng, reqs, uid_offset: int = 0):
    """Submit copies of ``reqs`` (uids shifted), drain, return
    ({base uid: Completion}, wall seconds)."""
    from repro.serve.api import Request
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(Request(uid=r.uid + uid_offset, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens,
                           adapter_id=r.adapter_id))
    done = eng.drain()
    wall = time.perf_counter() - t0
    return ({u - uid_offset: c for u, c in done.items() if u >= uid_offset},
            wall)


def check_completions(done, reqs, vocab: int) -> None:
    check(sorted(done) == sorted(r.uid for r in reqs),
          f"finished {sorted(done)}, submitted {[r.uid for r in reqs]}")
    for u, c in done.items():
        check(c.finish_reason == "length" and c.n_tokens == MAX_NEW,
              f"request {u}: {c.finish_reason!r} after {c.n_tokens} tokens")
        check(all(0 <= t < vocab for t in c.tokens),
              f"request {u}: token outside [0, {vocab})")


def serve_phase(cfg, params, adapters, reqs, dev) -> None:
    """The paged engine at published widths: a cold pass (compiles every
    step signature), then the same traffic warm with the prefix index
    emptied first, so both passes schedule identically."""
    from repro.serve.api import make_engine
    eng = make_engine(cfg, params, adapters, mode="paged",
                      max_slots=MAX_SLOTS, max_len=MAX_LEN,
                      page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK)
    cold, cold_s = serve(eng, reqs)
    check_completions(cold, reqs, cfg.vocab_size)
    st = eng.stats()
    check(st.prefix_cache.hits > 0, "the shared-prefix requests missed "
          "the prefix cache")
    check(st.moe.dropped_tokens == 0,
          f"{st.moe.dropped_tokens} MoE tokens dropped")
    n_sigs = st.compile.compiled_steps
    eng.release_prefix_cache()
    warm, warm_s = serve(eng, reqs, uid_offset=1000)
    check_completions(warm, reqs, cfg.vocab_size)
    st = eng.stats()
    same = sum(warm[u].tokens == cold[u].tokens for u in cold)
    toks = sum(c.n_tokens for c in warm.values())
    print(f"serve: {len(reqs)} requests x {MAX_NEW} tokens; cold pass "
          f"{cold_s:.2f}s, warm pass {warm_s:.2f}s (compile ~"
          f"{cold_s - warm_s:.2f}s); warm {toks / warm_s:.1f} tok/s")
    print(f"serve: {n_sigs} step signatures after the cold pass, "
          f"{st.compile.compiled_steps} after the warm pass "
          f"{list(st.compile.step_signatures)}")
    print(f"serve: prefix cache hits={st.prefix_cache.hits} "
          f"hit_tokens={st.prefix_cache.hit_tokens}; warm tokens equal to "
          f"cold in {same}/{len(cold)} requests; peak_bytes_in_use="
          f"{peak_bytes(dev)}")


def _forward_fn(cfg, exec_cfg=None):
    from repro.models import transformer as tfm
    ec = exec_cfg or tfm.ExecConfig()
    return jax.jit(lambda p, t: tfm.forward(cfg, p, {"tokens": t},
                                            exec_cfg=ec)[0])


def logit_error(got, ref) -> float:
    """Worst position's relative L2 distance between logit vectors."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    num = np.linalg.norm(got - ref, axis=-1)
    den = np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)
    return float(np.max(num / den))


def cpu_reference(cfg, params, tokens):
    """float32 logits on the host CPU, plus those of the same model with
    layer CONTROL_LAYER removed (its attention output and FFN down
    projections zeroed), the negative control for LOGIT_RTOL."""
    cpu = jax.devices("cpu")[0]
    p = jax.device_put(params, cpu)
    t = jax.device_put(tokens, cpu)
    fwd = _forward_fn(cfg)
    with jax.default_matmul_precision("highest"):
        ref = fwd(p, t)
        layer = dict(p["layers"][0])
        layer["attn"] = {**layer["attn"],
                         "wo": layer["attn"]["wo"].at[CONTROL_LAYER].set(0)}
        layer["ff"] = {**layer["ff"],
                       "w2": layer["ff"]["w2"].at[CONTROL_LAYER].set(0)}
        bad = fwd({**p, "layers": (layer,) + tuple(p["layers"][1:])}, t)
    return np.asarray(ref), np.asarray(bad)


def reference_phase(cfg, params, seed: int, dev) -> None:
    tokens = jnp.asarray(np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, (1, REF_TOKENS), np.int32))
    chip = np.asarray(_forward_fn(cfg)(params, tokens))
    ref, bad = cpu_reference(cfg, params, tokens)
    check(np.isfinite(chip).all(), "non-finite chip logits")
    err, err_bad = logit_error(chip, ref), logit_error(chip, bad)
    print(f"reference: {REF_TOKENS}-token forward, chip vs host-CPU float32 "
          f"logits: rel L2 {err:.3e} (bound {LOGIT_RTOL:.0e}); with layer "
          f"{CONTROL_LAYER} removed: {err_bad:.3e}; peak_bytes_in_use="
          f"{peak_bytes(dev)}")
    check(err <= LOGIT_RTOL, f"chip logits off the reference by {err:.3e}")
    check(err_bad > LOGIT_RTOL, "the logit bound cannot tell a missing "
          f"layer apart ({err_bad:.3e})")


def train_phase(seed: int, dev) -> None:
    """Three LoRA steps through the training launcher. Two microbatches of
    two: a batch of 4 x 512 in one pass needs 16.8 GB of HBM at these
    widths (the compiler's own count), more than one v5e holds."""
    from repro.launch.train import main as train_main
    t0 = time.perf_counter()
    log = train_main(["--arch", ARCH, "--steps", "3", "--batch", "4",
                      "--seq", "512", "--microbatches", "2",
                      "--seed", str(seed)])
    wall = time.perf_counter() - t0
    losses = [r["loss"] for r in log]
    check(len(losses) == 3, f"{len(losses)} training steps logged")
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    print(f"train: 3 steps in {wall:.2f}s (step 1 holds the compile); "
          f"step seconds {[round(r['sec'], 3) for r in log]}; losses "
          f"{losses}; peak_bytes_in_use={peak_bytes(dev)}")


def _devices_of(tree) -> set:
    return {s.device for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards}


def tp_phase(cfg, params, adapters, reqs, tp: int, dev) -> None:
    """Tensor-parallel serving against the single-chip engine, in one
    process: tokens (printed), placement over ``tp`` devices, and the
    single-forward logits under the engine's shardings (checked)."""
    from repro.serve.api import ParallelConfig, make_engine
    kw = dict(mode="paged", max_slots=MAX_SLOTS, max_len=MAX_LEN,
              page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK)
    one = make_engine(cfg, params, adapters, **kw)
    base, base_s = serve(one, reqs)
    check_completions(base, reqs, cfg.vocab_size)
    eng = make_engine(cfg, params, adapters, parallel=ParallelConfig(tp=tp),
                      **kw)
    got, got_s = serve(eng, reqs)
    check_completions(got, reqs, cfg.vocab_size)
    st = eng.stats().parallel
    check(st.tp == tp and len(set(st.devices)) == tp,
          f"mesh devices {st.devices}")
    spread = _devices_of(eng.params) | _devices_of(eng.cache)
    check(len(spread) == tp, f"shards on {len(spread)} devices, not {tp}")
    full_kv = sum(l.nbytes for l in jax.tree.leaves(eng.cache))
    check(st.kv_bytes_per_device * tp == full_kv,
          f"KV pool not split {tp} ways: {st.kv_bytes_per_device} bytes "
          f"per device of {full_kv}")
    same = sum(got[u].tokens == base[u].tokens for u in base)
    print(f"tp: tp={tp} over {list(st.devices)}; param bytes/device "
          f"{st.param_bytes_per_device}, KV bytes/device "
          f"{st.kv_bytes_per_device} (of {full_kv}); shards on "
          f"{len(spread)} devices")
    print(f"tp: greedy tokens equal to tp=1 in {same}/{len(base)} requests "
          f"(tp=1 {base_s:.2f}s, tp={tp} {got_s:.2f}s, both cold)")
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, REF_TOKENS), np.int32))
    ref = np.asarray(_forward_fn(cfg)(params, tokens))
    sharded = np.asarray(_forward_fn(cfg, eng.ec)(eng.params, tokens))
    err = logit_error(sharded, ref)
    print(f"tp: {REF_TOKENS}-token forward, tp={tp} vs one chip logits: "
          f"rel L2 {err:.3e} (bound {LOGIT_RTOL:.0e}); peak_bytes_in_use="
          f"{peak_bytes(dev)}")
    check(err <= LOGIT_RTOL, f"tp={tp} logits off one chip by {err:.3e}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="> 1: run only tensor-parallel serving over this "
                         "many chips, against one chip")
    args = ap.parse_args(argv)
    dev = require_tpu()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    print(f"device: {dev.device_kind} x{jax.device_count()}; jax "
          f"{jax.__version__}; compile cache {use_compile_cache()}")
    cfg = get_config(ARCH)
    reqs = make_requests(cfg.vocab_size, args.seed)
    t0 = time.perf_counter()
    params, adapters = build_model(cfg, args.seed)
    jax.block_until_ready(params)
    print(f"model: {ARCH} built in {time.perf_counter() - t0:.2f}s, "
          f"{sum(l.nbytes for l in jax.tree.leaves(params))} param bytes")
    if args.tp > 1:
        tp_phase(cfg, params, adapters, reqs, args.tp, dev)
    else:
        serve_phase(cfg, params, adapters, reqs, dev)
        reference_phase(cfg, params, args.seed, dev)
        del params, adapters
        gc.collect()
        jax.clear_caches()
        train_phase(args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()

"""Serving throughput: paged arena + chunked prefill vs the dense
``max_batch x max_len`` baseline, at 16+ concurrent mixed-length requests
and 4 LoRA adapters hot (paper SS V.G multi-task serving).

Reports decode tokens/s (steady-state, measured on a second pass so every
jit signature is warm), per-request p50/p99 completion latency, KV arena
bytes, and the engine's compile accounting (the paged step must compile
once per (chunk-bucket, table-width-bucket) pair, never per prompt length).

A second, shared-prefix workload (N requests drawn from a handful of
prompt families — the system-prompt serving pattern) measures the
copy-on-write prefix cache: prefill tokens actually computed, prefix-hit
rate, CoW forks, and peak KV pages vs the same paged engine with the
cache disabled; greedy outputs are checked token-identical to the dense
oracle.

A third workload reruns the shared-prefix traffic with speculative
decoding on (n-gram drafter over the same engine): reports draft accept
rate, rolled-back tokens/pages, and decode tok/s vs the spec-off engine —
with the same dense-oracle greedy-equivalence check (speculation must
change speed, never output).

A fourth workload measures tensor-parallel paged decode: the same engine
at tp=1 vs tp=2 on forced host devices (a subprocess, so this process
keeps one device), reporting decode tok/s, per-device KV bytes, and the
token-equality check — TP must change placement, never output.

A fifth workload serves an MoE model (reduced llama4-scout) through the
paged engine under both MoE dispatch modes: dropless (the serving
default — tokens can never drop, so greedy output is invariant to
prefill chunking) vs the capacity-bucketed baseline. Reports decode
tok/s for both, asserts the dropless engine's ``dropped_tokens`` stat is
exactly 0 and its greedy tokens match the dense whole-prompt oracle, and
records how many (token, expert) assignments the capacity baseline
dropped on the same traffic (the bug dropless closes).

A sixth workload runs speculative decoding on a hybrid Mamba+attention
arch (reduced jamba): every rejected draft exercises the SlotStateArena
checkpoint/restore and the full recurrent rollback-and-replay path.
Reports accept rate, recurrent rollback count, and decode tok/s vs the
same engine with spec off — with the dense-oracle greedy-equivalence
check (checkpointed recurrent state must change speed, never output).
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import emit, save_json
from repro.configs import get_config, reduce_config
from repro.core import lora as lora_lib
from repro.models import kvcache
from repro.models.transformer import init_params
from repro.serve.api import Request
from repro.serve.engine import DenseServeEngine, PagedServeEngine
from repro.serve.spec import SpecConfig


def _requests(n, vocab, rng, max_new):
    reqs = []
    for i in range(n):
        plen = int(rng.integers(6, 64))
        reqs.append(dict(uid=i,
                         prompt=rng.integers(0, vocab, plen).astype(np.int32),
                         max_new_tokens=max_new, adapter_id=i % 4))
    return reqs


def _family_requests(n, vocab, rng, max_new, families=4, head_len=48):
    """Shared-prefix traffic: every request's prompt starts with its
    family's common head (per-family adapter, so prefixes are shareable)."""
    heads = [rng.integers(0, vocab, head_len).astype(np.int32)
             for _ in range(families)]
    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab,
                            int(rng.integers(4, 12))).astype(np.int32)
        reqs.append(dict(uid=i,
                         prompt=np.concatenate([heads[i % families], tail]),
                         max_new_tokens=max_new, adapter_id=i % families))
    return reqs


def _page_bytes(cache, num_pages):
    """Bytes one pool page costs across every paged (kp/vp) leaf."""
    total = 0
    for entry in cache["layers"]:
        for name, leaf in entry.items():
            if name in ("kp", "vp"):
                total += leaf.size * leaf.dtype.itemsize
    return total // num_pages


def _drive(make_engine, reqs, warm_passes=1):
    """Warm + measure passes over ONE engine instance (per-instance jax.jit
    caches): warm passes compile every jit signature — greedy decode is
    deterministic, so the measured pass re-hits exactly the same shapes —
    the final pass measures wall time and per-request completion latency.
    Engines with the prefix cache on need warm_passes=2: the cache is empty
    on pass 1 and saturated from pass 2 onward, so only pass 2 schedules
    (and compiles) the same chunk shapes the measured pass will re-hit."""
    eng = make_engine()

    def one_pass(uid_off):
        for r in reqs:
            eng.submit(Request(**{**r, "uid": r["uid"] + uid_off}))
        t0 = time.perf_counter()
        done_at = {}
        ticks = 0
        while (eng.queue or (eng.sched.active() if hasattr(eng, "sched")
                             else any(eng.slot_req))) and ticks < 100_000:
            eng.step()
            ticks += 1
            now = time.perf_counter() - t0
            for uid in eng.finished:
                if uid >= uid_off:
                    done_at.setdefault(uid, now)
        wall = time.perf_counter() - t0
        total_new = sum(len(r.generated) for u, r in eng.finished.items()
                        if u >= uid_off)
        lats = np.asarray([done_at[u] for u in sorted(done_at)])
        return dict(wall_s=wall, ticks=ticks, new_tokens=total_new,
                    tok_per_s=total_new / wall,
                    p50_s=float(np.percentile(lats, 50)),
                    p99_s=float(np.percentile(lats, 99)))

    for p in range(warm_passes):     # warm-up: compiles every signature
        one_pass((p + 1) * 100_000)
    return eng, one_pass((warm_passes + 1) * 100_000)  # measured: warm


_TP_PROG = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import json, time
import jax, numpy as np
from repro.configs import get_config, reduce_config
from repro.core import lora as lora_lib
from repro.models.transformer import init_params
from repro.serve.api import ParallelConfig, Request, make_engine

spec = json.loads(os.environ['TP_BENCH_SPEC'])
cfg = reduce_config(get_config('llama3.2-1b'))
key = jax.random.PRNGKey(0)
params = init_params(cfg, key)
adapters = [lora_lib.init_lora_params(cfg, jax.random.fold_in(key, i + 1))
            for i in range(4)]
rng = np.random.default_rng(0)
reqs = [dict(uid=i,
             prompt=rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(6, 48))).astype(np.int32),
             max_new_tokens=spec['max_new'], adapter_id=i % 4)
        for i in range(spec['n_req'])]

out = {}
for tp in spec['tps']:
    eng = make_engine(cfg, params, adapters, mode='paged',
                      max_slots=spec['max_slots'], max_len=spec['max_len'],
                      page_size=16, prefill_chunk=32,
                      parallel=ParallelConfig(tp=tp))
    for off in (0, 100_000):             # pass 1 warms every jit signature
        for r in reqs:
            eng.submit(Request(**{**r, 'uid': r['uid'] + off}))
        t0 = time.perf_counter()
        done = eng.drain()
        wall = time.perf_counter() - t0
    toks = sum(c.n_tokens for c in done.values())
    st = eng.stats()
    full_kv = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                  for l in jax.tree.leaves(eng.cache))
    out[str(tp)] = {
        'tok_per_s': toks / wall, 'wall_s': wall,
        'kv_bytes_per_device': (st.parallel.kv_bytes_per_device
                                if tp > 1 else full_kv),
        'param_bytes_per_device': st.parallel.param_bytes_per_device,
        'tokens': {str(u): list(c.tokens) for u, c in done.items()},
    }
print(json.dumps(out))
"""


def _tp_workload(smoke):
    """tp=1 vs tp=2 paged decode on forced host devices (subprocess: the
    bench process itself keeps exactly one device). The child is pinned to
    the CPU, so this never takes a chip, and its numbers are CPU numbers
    on every host (``"platform": "cpu"``)."""
    spec = dict(tps=[1, 2], n_req=8 if smoke else 16,
                max_new=8 if smoke else 16, max_slots=8, max_len=256)
    env = {**os.environ,
           "PYTHONPATH": str(pathlib.Path(__file__).resolve().parents[1]
                             / "src"),
           "JAX_PLATFORMS": "cpu",
           "TP_BENCH_SPEC": json.dumps(spec)}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _TP_PROG], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    identical = out["1"]["tokens"] == out["2"]["tokens"]
    assert identical, "tp=2 greedy decode diverged from tp=1"
    return {
        "platform": "cpu",
        "tps": spec["tps"],
        "tok_per_s": {tp: out[tp]["tok_per_s"] for tp in out},
        "kv_bytes_per_device": {tp: out[tp]["kv_bytes_per_device"]
                                for tp in out},
        "param_bytes_per_device": {tp: out[tp]["param_bytes_per_device"]
                                   for tp in out},
        "tokens_identical_across_tp": identical,
    }


def run():
    smoke = os.environ.get("BENCH_SMOKE", "0") == "1"
    cfg = reduce_config(get_config("llama3.2-1b"))
    n_req, max_new = (16, 8) if smoke else (24, 24)
    max_len, max_slots, page = (256, 16, 16) if smoke else (1024, 16, 16)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    adapters = [lora_lib.init_lora_params(cfg, jax.random.fold_in(key, i + 1))
                for i in range(4)]
    rng = np.random.default_rng(0)
    reqs = _requests(n_req, cfg.vocab_size, rng, max_new)
    # pool sized for the mixed traffic, a fraction of the dense arena
    num_pages = max_slots * (64 + max_new + page) // page

    dense_eng, dense = _drive(
        lambda: DenseServeEngine(cfg, params, adapters=adapters,
                                 max_batch=max_slots, max_len=max_len), reqs)
    # cache off here: this workload has no prompt overlap to exploit, and
    # apples-to-apples vs dense means the PR-1 baseline configuration (the
    # prefix cache is measured on the shared-prefix workload below)
    paged_eng, paged = _drive(
        lambda: PagedServeEngine(cfg, params, adapters=adapters,
                                 max_slots=max_slots, max_len=max_len,
                                 page_size=page, num_pages=num_pages,
                                 prefill_chunk=32,
                                 enable_prefix_cache=False), reqs)

    stats = paged_eng.stats().as_dict()
    speedup = paged["tok_per_s"] / dense["tok_per_s"]
    dense_bytes = kvcache.cache_bytes(dense_eng.cache)
    paged_bytes = kvcache.cache_bytes(paged_eng.cache)
    max_sigs = (len(paged_eng.chunk_buckets) * len(paged_eng.block_buckets))
    bucketed = stats["compiled_steps"] <= max_sigs
    assert bucketed, (stats["step_signatures"], max_sigs)
    assert stats["jit_cache_size"] == stats["compiled_steps"], stats

    # ---- shared-prefix workload: prefix cache ON vs OFF (the PR-1
    # baseline), dense oracle for greedy equivalence
    srng = np.random.default_rng(1)
    sreqs = _family_requests(n_req, cfg.vocab_size, srng, max_new,
                             families=4)
    nocache_eng, nocache = _drive(
        lambda: PagedServeEngine(cfg, params, adapters=adapters,
                                 max_slots=max_slots, max_len=max_len,
                                 page_size=page, num_pages=num_pages,
                                 prefill_chunk=32,
                                 enable_prefix_cache=False), sreqs)
    shared_eng, shared = _drive(
        lambda: PagedServeEngine(cfg, params, adapters=adapters,
                                 max_slots=max_slots, max_len=max_len,
                                 page_size=page, num_pages=num_pages,
                                 prefill_chunk=32), sreqs, warm_passes=2)
    oracle_eng, _ = _drive(
        lambda: DenseServeEngine(cfg, params, adapters=adapters,
                                 max_batch=max_slots, max_len=max_len), sreqs)
    # uids are offset per pass; greedy decode is deterministic, so every
    # pass of either engine must produce the base request's tokens
    identical = all(
        shared_eng.finished[u].generated
        == oracle_eng.finished[100_000 + u % 100_000].generated
        for u in shared_eng.finished)
    assert identical, "prefix-shared paged decode diverged from dense oracle"

    # ---- spec-decode workload: same shared-prefix traffic, n-gram
    # drafter on vs off (both with the prefix cache), dense oracle check
    spec_eng, spec = _drive(
        lambda: PagedServeEngine(cfg, params, adapters=adapters,
                                 max_slots=max_slots, max_len=max_len,
                                 page_size=page, num_pages=num_pages,
                                 prefill_chunk=32,
                                 spec=SpecConfig(k=4, drafter="ngram")),
        sreqs, warm_passes=2)
    spec_identical = all(
        spec_eng.finished[u].generated
        == oracle_eng.finished[100_000 + u % 100_000].generated
        for u in spec_eng.finished)
    assert spec_identical, "spec-on greedy decode diverged from dense oracle"

    ns, ss = nocache_eng.stats().as_dict(), shared_eng.stats().as_dict()
    pb = _page_bytes(shared_eng.cache, num_pages)
    # counters accumulate over every pass (nocache ran 2, shared ran 3);
    # compare per-pass averages — the shared average still includes its
    # cold first pass, so this UNDERstates the steady-state reduction
    prefill_reduction = (ns["prefill_tokens"] / 2) / max(
        ss["prefill_tokens"] / 3, 1)
    hit_rate = ss["prefix_hit_tokens"] / max(
        ss["prefix_hit_tokens"] + ss["prefill_tokens"], 1)
    kv_peak_nocache = ns["peak_pages"] * pb
    kv_peak_shared = ss["peak_pages"] * pb

    emit("serve_dense", dense["wall_s"] * 1e6 / max(dense["ticks"], 1),
         f"tok/s={dense['tok_per_s']:.1f}_p99={dense['p99_s']*1e3:.0f}ms")
    emit("serve_paged", paged["wall_s"] * 1e6 / max(paged["ticks"], 1),
         f"tok/s={paged['tok_per_s']:.1f}_p99={paged['p99_s']*1e3:.0f}ms")
    emit("serve_speedup", 0.0,
         f"{speedup:.2f}x_decode_throughput_"
         f"{'PASS' if speedup >= 2 else 'BELOW'}_2x_target_"
         f"kv_bytes_{dense_bytes/max(paged_bytes,1):.1f}x_smaller")
    emit("serve_prefix_cache", 0.0,
         f"prefill_reduction_{prefill_reduction:.2f}x_"
         f"{'PASS' if prefill_reduction >= 2 else 'BELOW'}_2x_target_"
         f"hit_rate_{hit_rate:.2f}_"
         f"kv_peak_{kv_peak_nocache/max(kv_peak_shared,1):.2f}x_smaller")
    sp = spec_eng.stats().as_dict()
    spec_speedup = spec["tok_per_s"] / max(shared["tok_per_s"], 1e-9)
    # every verify step emits accepted_in_row + 1 tokens, so the number of
    # verify steps is decode_tokens - accepted_tokens: this ratio is the
    # step-compression factor verification buys (the memory-bound decode
    # steps saved — the win wall-clock can't see at smoke model sizes,
    # where per-tick host overhead dominates the step itself)
    tokens_per_step = (sp["decode_tokens"]
                       / max(sp["decode_tokens"] - sp["accepted_tokens"], 1))
    emit("serve_spec_decode", 0.0,
         f"accept_rate_{sp['spec_accept_rate']:.2f}_"
         f"tokens_per_decode_step_{tokens_per_step:.2f}_"
         f"wall_speedup_{spec_speedup:.2f}x_"
         f"oracle_{'PASS' if spec_identical else 'DIVERGED'}")

    # ---- MoE workload: dropless (serving default) vs capacity dispatch
    # on a reduced llama4-scout, dense oracle for greedy equivalence.
    # Prompt widths 6..48 under prefill_chunk=8 land real capacity drops
    # at the default capacity_factor (1.25): C = ceil(8*1.25/4) = 3 rows
    # for an 8-wide top-1 chunk over 4 reduced experts.
    mcfg = reduce_config(get_config("llama4-scout-17b-a16e"))
    mparams = init_params(mcfg, jax.random.PRNGKey(1))
    mrng = np.random.default_rng(2)
    m_req, m_new = (6, 6) if smoke else (12, 10)
    mreqs = [dict(uid=i,
                  prompt=mrng.integers(1, mcfg.vocab_size,
                                       int(mrng.integers(6, 48)))
                  .astype(np.int32),
                  max_new_tokens=m_new) for i in range(m_req)]
    moe_kw = dict(max_slots=8, max_len=128, page_size=8, prefill_chunk=8,
                  enable_prefix_cache=False)
    dropless_eng, dropless = _drive(
        lambda: PagedServeEngine(mcfg, mparams, **moe_kw), mreqs)
    capacity_eng, capacity = _drive(
        lambda: PagedServeEngine(mcfg, mparams, moe_dispatch="capacity",
                                 **moe_kw), mreqs)
    moracle_eng, _ = _drive(
        lambda: DenseServeEngine(mcfg, mparams, max_batch=8, max_len=128),
        mreqs)
    moe_dl = dropless_eng.stats()
    moe_cap = capacity_eng.stats()
    assert moe_dl.moe.dropped_tokens == 0, \
        "dropless serving dropped MoE tokens"
    moe_identical = all(
        dropless_eng.finished[u].generated
        == moracle_eng.finished[100_000 + u % 100_000].generated
        for u in dropless_eng.finished)
    assert moe_identical, "dropless MoE decode diverged from dense oracle"

    # ---- spec-on-hybrid workload: speculative decoding on a recurrent
    # (Mamba+attention) arch. Every rejected draft goes through the
    # SlotStateArena checkpoint/restore and the rollback-and-replay path,
    # so greedy equivalence vs the dense engine is the real acceptance bar.
    hcfg = reduce_config(get_config("jamba-1.5-large-398b"))
    hparams = init_params(hcfg, jax.random.PRNGKey(2))
    hrng = np.random.default_rng(3)
    h_req, h_new = (5, 10) if smoke else (10, 16)
    # motif-tiled prompts: repetitive enough that the n-gram drafter gets
    # real acceptances, so both accept and reject paths are measured
    hreqs = []
    for i in range(h_req):
        motif = hrng.integers(1, hcfg.vocab_size, 3).astype(np.int32)
        hreqs.append(dict(uid=i,
                          prompt=np.tile(motif, int(hrng.integers(3, 8))),
                          max_new_tokens=h_new))
    hyb_kw = dict(max_slots=4, max_len=64, page_size=8, prefill_chunk=8)
    hyb_off_eng, hyb_off = _drive(
        lambda: PagedServeEngine(hcfg, hparams, **hyb_kw), hreqs)
    hyb_on_eng, hyb_on = _drive(
        lambda: PagedServeEngine(hcfg, hparams,
                                 spec=SpecConfig(k=4, drafter="ngram"),
                                 **hyb_kw), hreqs)
    horacle_eng, _ = _drive(
        lambda: DenseServeEngine(hcfg, hparams, max_batch=4, max_len=64),
        hreqs)
    hst = hyb_on_eng.stats()
    assert hst.spec.enabled and hst.spec.disabled_reason is None
    hsd = hst.as_dict()
    hyb_identical = all(
        hyb_on_eng.finished[u].generated
        == horacle_eng.finished[100_000 + u % 100_000].generated
        for u in hyb_on_eng.finished)
    assert hyb_identical, "spec-on hybrid decode diverged from dense oracle"

    # ---- tensor-parallel workload (subprocess with 4 forced devices)
    tp = _tp_workload(smoke)
    kv1, kv2 = (tp["kv_bytes_per_device"][k] for k in ("1", "2"))
    emit("serve_tp", 0.0,
         f"platform={tp['platform']}_forced_host_devices_"
         f"tp2_tok/s={tp['tok_per_s']['2']:.1f}_"
         f"tp1_tok/s={tp['tok_per_s']['1']:.1f}_"
         f"kv/dev_{kv1/max(kv2,1):.1f}x_smaller_"
         f"tokens_{'PASS' if tp['tokens_identical_across_tp'] else 'DIVERGED'}")
    emit("serve_moe_dropless", 0.0,
         f"dropless_tok/s={dropless['tok_per_s']:.1f}_"
         f"capacity_tok/s={capacity['tok_per_s']:.1f}_"
         f"dropped_0_vs_{moe_cap.moe.dropped_tokens}_"
         f"oracle_{'PASS' if moe_identical else 'DIVERGED'}")
    emit("serve_spec_hybrid", 0.0,
         f"accept_rate_{hsd['spec_accept_rate']:.2f}_"
         f"recurrent_rollbacks_{hsd['spec_recurrent_rollbacks']}_"
         f"tok/s_on_{hyb_on['tok_per_s']:.1f}_off_{hyb_off['tok_per_s']:.1f}_"
         f"oracle_{'PASS' if hyb_identical else 'DIVERGED'}")

    payload = {
        "smoke": smoke,
        "workload": {"n_requests": n_req, "adapters": 4,
                     "prompt_lens": "6..64 mixed", "max_new": max_new,
                     "max_len": max_len, "max_slots": max_slots},
        "dense": {**dense, "kv_bytes": dense_bytes},
        "paged": {**paged, "kv_bytes": paged_bytes,
                  "page_size": page, "num_pages": num_pages,
                  "compiled_steps": stats["compiled_steps"],
                  "step_signatures": [list(s) for s in
                                      stats["step_signatures"]],
                  "max_signatures": max_sigs,
                  "preemptions": stats["preemptions"],
                  "peak_pages": stats["peak_pages"]},
        "decode_throughput_speedup": speedup,
        "meets_2x_target": bool(speedup >= 2),
        "shared_prefix": {
            "workload": {"n_requests": n_req, "families": 4,
                         "head_len": 48, "tail_lens": "4..12"},
            "nocache": {**nocache,
                        "prefill_tokens": ns["prefill_tokens"],
                        "peak_pages": ns["peak_pages"],
                        "kv_peak_bytes": kv_peak_nocache},
            "prefix_cache": {**shared,
                             "prefill_tokens": ss["prefill_tokens"],
                             "prefix_hit_tokens": ss["prefix_hit_tokens"],
                             "prefix_hits": ss["prefix_hits"],
                             "cow_forks": ss["cow_forks"],
                             "shared_pages": ss["shared_pages"],
                             "index_pages": ss.get("index_pages", 0),
                             "peak_pages": ss["peak_pages"],
                             "kv_peak_bytes": kv_peak_shared},
            "prefill_token_reduction": prefill_reduction,
            "prefix_hit_rate": hit_rate,
            "meets_2x_prefill_reduction": bool(prefill_reduction >= 2),
            "greedy_matches_dense_oracle": bool(identical),
        },
        "spec_decode": {
            "drafter": "ngram", "k": 4,
            "spec_on": {**spec,
                        "spec_steps": sp["spec_steps"],
                        "drafted_tokens": sp["drafted_tokens"],
                        "accepted_tokens": sp["accepted_tokens"],
                        "rolled_back_tokens": sp["rolled_back_tokens"],
                        "rolled_back_pages": sp["rolled_back_pages"]},
            "spec_off_tok_per_s": shared["tok_per_s"],
            "accept_rate": sp["spec_accept_rate"],
            "tokens_per_decode_step": tokens_per_step,
            "decode_throughput_speedup": spec_speedup,
            "greedy_matches_dense_oracle": bool(spec_identical),
        },
        "spec_hybrid": {
            "arch": "jamba-1.5-large-398b (reduced)",
            "drafter": "ngram", "k": 4,
            "workload": {"n_requests": h_req, "prompt_lens": "4..24",
                         "max_new": h_new, "prefill_chunk": 8},
            "spec_on": {**hyb_on,
                        "drafted_tokens": hsd["drafted_tokens"],
                        "accepted_tokens": hsd["accepted_tokens"],
                        "rolled_back_tokens": hsd["rolled_back_tokens"],
                        "recurrent_rollbacks":
                            hsd["spec_recurrent_rollbacks"]},
            "spec_off_tok_per_s": hyb_off["tok_per_s"],
            "accept_rate": hsd["spec_accept_rate"],
            "greedy_matches_dense_oracle": bool(hyb_identical),
        },
        "tensor_parallel": tp,
        "moe_dropless": {
            "arch": "llama4-scout-17b-a16e (reduced)",
            "workload": {"n_requests": m_req, "prompt_lens": "6..48",
                         "max_new": m_new, "prefill_chunk": 8},
            "dropless": {**dropless,
                         "dropped_tokens": moe_dl.moe.dropped_tokens},
            "capacity": {**capacity,
                         "dropped_tokens": moe_cap.moe.dropped_tokens},
            "dropless_over_capacity_tok_per_s":
                dropless["tok_per_s"] / max(capacity["tok_per_s"], 1e-9),
            "capacity_dropped_tokens": moe_cap.moe.dropped_tokens,
            "greedy_matches_dense_oracle": bool(moe_identical),
        },
    }
    save_json("serve_throughput", payload)
    return payload


if __name__ == "__main__":
    run()

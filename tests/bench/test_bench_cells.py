"""Whole runs of a serving and a training cell at a tiny size on the CPU:
the harness past its look for a chip, the control, and the faults that
``correct`` has to catch."""
import json
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import Run, run_cell
from bench.spec import BENCH, load_cell

CONFIG = {
    "name": "tiny-dense", "source": "test", "family": "dense",
    "program_arch": "mistral-nemo-12b", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 257,
    "rms_norm_eps": 1e-05, "rope_theta": 1e6, "tie_word_embeddings": False,
    "lora": {"rank": 4, "alpha": 4, "targets": ["wq", "wv"], "adapters": 3},
    "serve": {"num_pages": 64},
    # the CPU computes in float32 like the reference: sound runs read ~0
    "limits": {"logit_gap": 1e-3, "loss_gap": 1e-4, "grad_gap": 1e-3,
               "update_gap": 1e-3}}
CHAT = {
    "kind": "serve", "rate_per_s": 6.0, "strata": 3, "adapter_zipf": 1.0,
    "system_prompts_per_adapter": 2, "system_prompt_tokens": 16,
    "user_tokens": {"median": 10, "sigma": 0.8, "min": 4, "max": 24},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 6, "max": 12},
    "engine": {"max_slots": 4, "max_len": 64, "page_size": 8,
               "prefill_chunk": 16},
    "check": {"min_requests": 4, "min_tokens": 30},
    "trace": {"start_fraction": 0.3, "seconds": 0.5}}
SFT = {
    "kind": "train", "seq_len": 32, "tokens_per_step": 128,
    "microbatch_rows": 2, "remat": True,
    "adamw": {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
              "grad_clip": 1.0},
    "check_steps": 3, "trace": {"start_fraction": 0.3, "steps": 2}}
E2E = [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
        "source": "host_clock"}] + [
    {"name": n, "unit": u, "better": "lower", "bound": 0.25,
     "source": "host_clock", "workloads": [w]}
    for n, u, w in (("ttft_p90_ms", "ms", "chat"), ("itl_p95_ms", "ms", "chat"),
                    ("out_tok_per_s", "tokens/s", "chat"),
                    ("train_tok_per_s", "tokens/s", "train"))]


@pytest.fixture(scope="module")
def cells(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tiny")
    root = tmp / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs" / "tiny-dense.json").write_text(json.dumps(CONFIG))
    (root / "traffic" / "tiny-chat.json").write_text(json.dumps(CHAT))
    (root / "traffic" / "tiny-sft.json").write_text(json.dumps(SFT))
    manifest = {"workloads": [
        {"name": "chat", "config": "tiny-dense", "traffic": "tiny-chat",
         "chips": 1, "why": "t"},
        {"name": "train", "config": "tiny-dense", "traffic": "tiny-sft",
         "chips": 1, "why": "t"}], "end_to_end": E2E, "per_layer": []}
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return {n: load_cell(n, tmp / "BENCHMARK.json", root)
            for n in ("chat", "train")}, tmp


def _run(cells, name, keep=None, **kw):
    cells, tmp = cells
    return run_cell(Run(cell=cells[name], seed=2 ** 31 + 17, seconds=1.5,
                        trace=False, t_start=time.perf_counter(),
                        peaks={"bf16_flops_per_s": 1e12}, out_dir=tmp / "out",
                        **kw), keep_data=keep)


def test_serve_cell_runs_correct_and_its_control_does_not(cells):
    line = _run(cells, "chat")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 9
    assert set(line["metrics"]) == {"setup_s", "ttft_p90_ms", "itl_p95_ms",
                                    "out_tok_per_s"}
    assert list(line)[-1] == "checks"
    limit = CONFIG["limits"]["logit_gap"]
    assert line["checks"]["logit_gap"]["value"] <= limit
    ctrl = _run(cells, "chat", control="bf16")
    print("serve bf16 control", ctrl["checks"])
    assert not ctrl["correct"]
    assert ctrl["checks"]["logit_gap"]["value"] > limit


def test_train_cell_runs_correct_and_its_control_does_not(cells):
    line = _run(cells, "train")
    assert line["correct"] and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_tok_per_s"}
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    ctrl = _run(cells, "train", control="bf16")
    print("train bf16 control", ctrl["checks"])
    assert not ctrl["correct"]


@pytest.mark.parametrize("name,control", [
    ("chat", "fp8"), ("train", "fp8"), ("train", "half_batch")])
def test_other_stand_ins_come_out_incorrect(cells, name, control):
    line = _run(cells, name, control=control)
    print(name, control, line["checks"])
    assert not line["correct"]


def _altered_tokens(eng):
    step = eng._step

    def bad(*a):
        toks, cache, dropped = step(*a)
        return (toks + 1) % CONFIG["vocab_size"], cache, dropped
    eng._step = bad


def _state_unchanged(eng):
    step = eng._step

    def bad(*a):
        kept = jax.tree.map(jnp.copy, a[2])
        toks, _, dropped = step(*a)
        return toks, kept, dropped
    eng._step = bad


def _train_state_unchanged(step):
    def bad(w, lora, opt, batch, rng):
        kept = jax.tree.map(jnp.copy, (lora, opt))
        _, _, m = step(w, lora, opt, batch, rng)
        return kept[0], kept[1], m
    return bad


def _half_batch(step):
    def bad(w, lora, opt, batch, rng):
        n = batch["tokens"].shape[0] // 2
        half = {k: jnp.concatenate([v[:n], v[:n]]) for k, v in batch.items()}
        return step(w, lora, opt, half, rng)
    return bad


@pytest.mark.parametrize("name,hook", [
    ("chat", {"wrap_engine": _altered_tokens}),
    ("chat", {"wrap_engine": _state_unchanged}),
    ("train", {"wrap_step": _train_state_unchanged}),
    ("train", {"wrap_step": _half_batch})],
    ids=["token_altered", "serve_state_unchanged", "train_state_unchanged",
         "half_batch"])
def test_faults_come_out_incorrect(cells, name, hook):
    assert not _run(cells, name, **hook)["correct"]

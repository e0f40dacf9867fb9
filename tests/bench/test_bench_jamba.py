"""The jamba2-3b configuration and its cell: found by name, laid out as the
program lays out its weights at full width, its FLOPs counted, and its
control and a planted fault caught at a CPU size."""
import json
import shutil
import time

import jax
import pytest

from bench import program
from bench.harness import Run, run_cell
from bench.spec import BENCH, load_cell, load_json, load_module

CELL = "jamba2-lora-train-8k"
JAMBA = load_json(BENCH / "configs" / "jamba2-3b.json")
REF = load_module(BENCH / "reference" / "jamba.py")


def test_the_cell_loads_by_name():
    cell = load_cell(CELL)
    assert cell.config["name"] == "jamba2-3b" and cell.kind == "train"
    assert cell.reference().__name__ == REF.__name__
    # 8192 tokens a step as one row of 8192, in one microbatch
    t = cell.traffic
    assert (t["tokens_per_step"], t["seq_len"], t["microbatch_rows"]) == (
        8192, 8192, 1) and t["remat"]
    # one traced step: the window counts it whole however long a step is
    assert t["trace"]["steps"] == 1
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "train_tok_per_s"]
    assert {m["name"] for m in cell.per_layer} == {"step_mfu.train",
                                                   "idle_share.train"}
    assert set(JAMBA["limits"]) == {"loss_gap", "grad_gap", "update_gap"}
    assert JAMBA["reduced"] == [] and JAMBA["num_hidden_layers"] == 28


def test_weights_have_the_programs_layout_at_full_width():
    """``check_layout`` of the full-width weights and adapter, by
    ``eval_shape`` alone: nothing is allocated."""
    cfg = program.model_config(JAMBA, REF)
    assert cfg.attn_layer_indices() == (7, 21) and cfg.hd == 128

    def check(k):
        program.check_layout(cfg, REF.make_weights(JAMBA, k),
                             REF.make_adapter(JAMBA, k))
    jax.eval_shape(check, jax.random.PRNGKey(0))
    shapes = jax.eval_shape(lambda k: REF.make_weights(JAMBA, k),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_029_337_472
    lora = jax.eval_shape(lambda k: REF.make_adapter(JAMBA, k),
                          jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(lora)) == 17_539_072


def test_train_step_flops():
    # jamba2-3b: d 2560, 20 heads of 128 over one KV head, d_inner 5120,
    # dt_rank 160, d_state 16, MLP 8192 on all 28 layers, tied head 65536
    d, q, kv, di, r, N, ff, V = 2560, 2560, 128, 5120, 160, 16, 8192, 65536
    attn = d * q * 2 + d * kv * 2                   # wq, wo, wk, wv
    mamba = d * 2 * di + di * (r + 2 * N) + r * di + di * d
    layers = 2 * attn + 26 * mamba + 28 * 3 * d * ff
    lora = 2 * 32 * (d + q + d + kv) + 26 * 32 * (d + 2 * di + di + d)
    assert lora == 17_539_072
    per_token = 4 * (layers + d * V) + 6 * lora
    causal = 3 * 4 * 20 * 128 * 2 * (8192 * 8193 // 2)
    want = 8192 * per_token + causal
    assert REF.train_step_flops(JAMBA, 1, 8192) == pytest.approx(want,
                                                                 rel=1e-12)
    assert REF.scan_flops_per_token(JAMBA) == 26 * 5120 * (7 * 16 + 4)
    assert REF.scan_bytes_per_token(JAMBA) == 26 * 4 * (4 * 5120 + 2 * 16)


# one period of the published pattern at a CPU size
TINY = {k: JAMBA[k] for k in (
    "attn_layer_offset", "attn_layer_period", "expert_layer_offset",
    "expert_layer_period", "hidden_act", "mamba_conv_bias", "mamba_d_conv",
    "mamba_expand", "mamba_proj_bias", "num_experts", "num_experts_per_tok",
    "rms_norm_eps", "tie_word_embeddings")}
TINY.update({
    "name": "tiny-jamba", "source": "test", "family": "jamba",
    "program_arch": "jamba2-3b", "num_hidden_layers": 14, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "intermediate_size": 128, "vocab_size": 257, "mamba_d_state": 4,
    "mamba_dt_rank": 8,
    "lora": {"rank": 4, "alpha": 4, "adapters": 1,
             "targets": ["wq", "wv", "mamba_in", "mamba_out"]},
    # the CPU computes in float32 like the reference: sound runs read ~0
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3}})
SFT = {"kind": "train", "seq_len": 32, "tokens_per_step": 64,
       "microbatch_rows": 2, "remat": True,
       "adamw": {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
                 "grad_clip": 1.0},
       "check_steps": 3, "trace": {"start_fraction": 0.3, "steps": 2}}


@pytest.mark.parametrize("ctrl", ["bf16", "fp8"])
def test_each_control_is_visibly_off(ctrl):
    """The reference one precision step down moves the hidden states."""
    import jax.numpy as jnp
    key = jax.random.PRNGKey(4)
    w = REF.make_weights(TINY, key)
    ad = REF.make_adapter(TINY, jax.random.fold_in(key, 1))
    tokens = jax.random.randint(jax.random.fold_in(key, 2), (40,), 0, 257)
    h = REF.hidden(TINY, w, ad, tokens)
    hc = REF.hidden(TINY, w, ad, tokens, ctrl)
    assert float(jnp.max(jnp.abs(hc - h))) > 1e-2


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tiny-jamba")
    root = tmp / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs" / "tiny-jamba.json").write_text(json.dumps(TINY))
    (root / "traffic" / "tiny-jamba-sft.json").write_text(json.dumps(SFT))
    manifest = {"workloads": [{"name": "train", "config": "tiny-jamba",
                               "traffic": "tiny-jamba-sft", "chips": 1,
                               "why": "t"}],
                "end_to_end": [
                    {"name": "setup_s", "unit": "s", "better": "lower",
                     "bound": 0.25, "source": "host_clock"},
                    {"name": "train_tok_per_s", "unit": "tokens/s",
                     "better": "higher", "bound": 0.01,
                     "source": "host_clock", "workloads": ["train"]}],
                "per_layer": []}
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return load_cell("train", tmp / "BENCHMARK.json", root), tmp


@pytest.mark.parametrize("control", ["", "fp8", "half_batch"])
def test_the_cell_is_correct_and_its_stand_ins_are_not(cell, control):
    c, tmp = cell
    line = run_cell(Run(cell=c, seed=2 ** 31 + 29, seconds=1.0, trace=False,
                        t_start=time.perf_counter(),
                        peaks={"bf16_flops_per_s": 1e12},
                        out_dir=tmp / "out", control=control))
    print(control or "program", line["checks"])
    assert line["correct"] == (control == "")


# a Mamba block's device time, split into its projections and its scan
SCAN_HLO = """\
HloModule jit_step

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %convolution.1 = f32[4]{0} convolution(%a, %a), metadata={op_name="jit(step)/jvp()/while/body/closed_call/recurrent/dot_general"}
  %multiply.2 = f32[4]{0} multiply(%convolution.1, %a), metadata={op_name="jit(step)/jvp()/while/body/closed_call/recurrent/ssm_scan/mul"}
  ROOT %dot.3 = f32[4]{0} dot(%multiply.2, %a), metadata={op_name="jit(step)/transpose(jvp())/while/body/recurrent/ssm_scan/dot_general"}
}
"""


def _scan_trace():
    from bench.tracing import Device, Trace
    ops = []
    for t0 in (1.0, 5.0):       # two complete steps of 3 s
        ops += [(t0, t0 + 1, "%convolution.1 = f32[4]{0} convolution(%a)"),
                (t0 + 1, t0 + 1.5, "%multiply.2 = f32[4]{0} multiply(%a)"),
                (t0 + 1.5, t0 + 3, "%dot.3 = f32[4]{0} dot(%a)")]
    dev = Device(ops=ops, modules=[(0.0, 0.5, "jit_step"),
                                   (1.0, 4.0, "jit_step"),
                                   (5.0, 8.0, "jit_step"),
                                   (9.0, 9.5, "jit_step")])
    return Trace(devices={"/device:TPU:0": dev}, spans=[], window=(0.0, 10.0))


def test_ssm_scan_reader_splits_the_mamba_block(monkeypatch):
    from bench import scopes
    from repro import obs
    data = {"kind": "train", "trace": _scan_trace(), "hlo_text": SCAN_HLO}
    reader = load_module(BENCH / "metrics" / "ssm_scan_ms.train.py")
    assert scopes.buckets(SCAN_HLO) == {"a": scopes.UNSCOPED,
                                        "convolution.1": "recurrent",
                                        "multiply.2": "ssm_scan",
                                        "dot.3": "ssm_scan"}
    assert reader.read(data) == pytest.approx(2000.0)     # ms per step
    assert scopes.train_ms(data, obs.RECURRENT) == pytest.approx(1000.0)
    # a program that names no scan scope reads nothing, and nothing raises
    monkeypatch.delattr(obs, "SSM_SCAN")
    assert reader.read(data) is None

"""Device time by the program's layers: the mapping from device ops to the
``repro.obs`` scopes, self times on a synthesized trace, the scopes' cover
of the compiled train and serving steps, and the join of a profiler trace's
op names to the compiled text."""
import collections
import dataclasses
import glob
import json
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import scopes
from bench.spec import BENCH, load_cell, load_module
from bench.tracing import Device, Trace, merge
from repro import obs


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


# ---------------------------------------------------------------------------
# scope names and self time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp()/while/body/closed_call/attn/dot_general", "attn"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "mlp"),
    ("jit(f)/transpose(jvp(loss))/while/body/attn/flash_fused/exp", "attn"),
    ("jit(f)/vmap(jvp(head))/dot_general", "head"),
    ("jit(step)/jvp()/while/body/dynamic_slice", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
    ("jit(step)/attention/mlpx/dot_general", scopes.UNSCOPED),
])
def test_innermost_layer_scope(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_instruction_names_from_event_names():
    assert scopes.instruction(
        "%fusion.16 = f32[4,64]{1,0:T(4,128)} fusion(%a), kind=kLoop") == \
        "fusion.16"
    assert scopes.instruction("dot_general.33") == "dot_general.33"


HLO = """\
HloModule jit_step

%f4 (x: f32[4]) -> (f32[4], f32[4]) {
  %x = f32[4]{0} parameter(0)
  %dot.1 = f32[4]{0} dot(%x, %x), metadata={op_name="jit(step)/transpose(jvp(head))/dot_general"}
  %reduce.2 = f32[4]{0} reduce(%dot.1), to_apply=%add, metadata={op_name="jit(step)/transpose(jvp(loss))/reduce_max"}
  ROOT %tuple.3 = (f32[4]{0}, f32[4]{0}) tuple(%dot.1, %reduce.2)
}

%f5 (y: f32[4]) -> f32[4] {
  %y = f32[4]{0} parameter(0)
  ROOT %convolution.7 = f32[4]{0} convolution(%y, %y), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/dot_general"}
}

%f6 (z: f32[4]) -> f32[4] {
  %z = f32[4]{0} parameter(0)
  ROOT %fusion.8 = f32[4]{0} fusion(%z), kind=kLoop, calls=%f5
}

%f7 (u: f32[4]) -> f32[4] {
  %u = f32[4]{0} parameter(0)
  %multiply.10 = f32[4]{0} multiply(%u, %u), metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/mul"}
  ROOT %add.11 = f32[4]{0} add(%multiply.10, %u), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/add"}
}

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/mlp/mul"}
  %fusion.6 = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%f7, metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/add"}
  ROOT %copy.3 = f32[4]{0} copy(%fusion.6), metadata={op_name="jit(step)/jvp()/while/body/dynamic_slice"}
}

ENTRY %main (a: f32[4]) -> (f32[4], f32[4]) {
  %a = f32[4]{0} parameter(0)
  %while.9 = f32[4]{0} while(%a), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp()/while"}
  %fusion.5 = f32[4]{0} fusion(%while.9), kind=kOutput, calls=%f6
  ROOT %fusion.4 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.5), kind=kOutput, calls=%f4, metadata={op_name="jit(step)/transpose(jvp())/head/dot_general"}
}
"""


def _op(s, e, name):
    return (s, e, f"%{name} = f32[4]{{0}} fusion(%p)")


@pytest.fixture
def trace():
    # window 0..20 s; two complete executions of jit_step (1-6, 8-13), one
    # cut by the profiler's start (0-0.5), one by its stop though inside
    # the window (18-18.001) and one other program (14-15). In each
    # complete one: a while (1-5) over attn (1-2), mlp (2-3.5), an
    # elementwise fusion of attn and mlp (3.5-3.75) and an unscoped copy
    # (4-4.5); then a fusion whose unscoped root holds an mlp matmul in a
    # nested fusion (5-5.5), and the head's matmul with the loss's
    # reduction fused in (5.5-6)
    ops = []
    for t0 in (1.0, 8.0):
        ops += [_op(t0, t0 + 4, "while.9"), _op(t0, t0 + 1, "fusion.1"),
                _op(t0 + 1, t0 + 2.5, "fusion.2"),
                _op(t0 + 2.5, t0 + 2.75, "fusion.6"),
                _op(t0 + 3, t0 + 3.5, "copy.3"),
                _op(t0 + 4, t0 + 4.5, "fusion.5"),
                _op(t0 + 4.5, t0 + 5, "fusion.4")]
    cut = [(0.0, 0.5), (14.0, 15.0), (18.0, 18.001)]
    ops += [_op(s, e, "fusion.1") for s, e in cut]
    dev = Device(ops=ops, modules=[(0.0, 0.5, "jit_step"),
                                   (1.0, 6.0, "jit_step"),
                                   (8.0, 13.0, "jit_step"),
                                   (14.0, 15.0, "jit_other"),
                                   (18.0, 18.001, "jit_step")])
    return Trace(devices={"/device:TPU:0": dev},
                 spans=[(0.0, 20.0, "trace_window")], window=(0.0, 20.0))


def test_a_fusion_counts_under_its_matmuls_layer():
    got = scopes.buckets(HLO)
    assert got["fusion.1"] == "attn" and got["fusion.2"] == "mlp"
    # a root without op_name takes the layer of what it fuses, nested too
    assert got["fusion.5"] == got["fusion.8"] == "mlp"
    # the head's matmul with the loss's reduction fused in is the head's
    assert got["fusion.4"] == "head"
    # elementwise work of two layers, whatever layer the root names
    assert got["fusion.6"] == "attn+mlp"
    assert got["copy.3"] == got["while.9"] == scopes.UNSCOPED


def test_self_times_add_up_to_busy_time(trace):
    n, ops = scopes.step_ops(trace, scopes.TRAIN_MODULE)
    assert n == 2 and len(ops) == 14
    per_op = scopes.self_times(ops)
    # the while keeps only its own overhead: 4 s less 1 + 1.5 + 0.25 + 0.5
    assert per_op["while.9"] == pytest.approx(2 * 0.75)
    busy = sum(e - s for s, e in merge((s, e) for s, e, _ in ops))
    assert busy == pytest.approx(2 * 5.0)
    sec = scopes.by_bucket(per_op, scopes.buckets(HLO))
    assert sec == {"attn": pytest.approx(2.0), "mlp": pytest.approx(4.0),
                   "attn+mlp": pytest.approx(0.5), "head": pytest.approx(1.0),
                   scopes.UNSCOPED: pytest.approx(2 * 0.75 + 2 * 0.5)}
    assert sum(sec.values()) == pytest.approx(busy)


@pytest.mark.parametrize("ops,want", [
    # b outruns its parent a: the second past a's end is b's
    ([(0.0, 2.0, "a"), (1.0, 3.0, "b"), (3.0, 4.0, "c")],
     {"a": 1.0, "b": 2.0, "c": 1.0}),
    # c outruns b, which nests in a; a resumes once b ends
    ([(0.0, 10.0, "a"), (1.0, 3.0, "b"), (2.0, 4.0, "c"), (6.0, 7.0, "d")],
     {"a": 6.0, "b": 1.0, "c": 2.0, "d": 1.0}),
    # two start together: the shorter is inside; a gap is nobody's
    ([(0.0, 1.0, "b"), (0.0, 2.0, "a"), (5.0, 6.0, "e")],
     {"a": 1.0, "b": 1.0, "e": 1.0}),
])
def test_self_time_of_an_op_that_outruns_its_parent(ops, want):
    got = scopes.self_times(ops)
    assert got == {k: pytest.approx(v) for k, v in want.items()}
    union = sum(e - s for s, e in merge((s, e) for s, e, _ in ops))
    assert sum(got.values()) == pytest.approx(union)


def test_readers_give_ms_per_complete_step(trace):
    data = {"kind": "train", "trace": trace, "hlo_text": HLO}
    want = {"attn_ms.train": 1000.0, "mlp_ms.train": 2000.0,
            "head_loss_ms.train": 500.0}
    assert set(want) == set(scopes.METRICS)
    for name, ms in want.items():
        assert _reader(name).read(data) == pytest.approx(ms)
    # a layer that never occurs reads None, not 0; a joint fusion counts
    # where all its layers are read, not for one of them alone
    assert scopes.train_ms(data, obs.OPTIMIZER) is None
    assert scopes.train_ms(data, obs.LOSS) is None
    assert scopes.train_ms(data, obs.ATTN, obs.MLP) == pytest.approx(3250.0)
    # nothing to read: a serving run, or no compiled text
    assert _reader("attn_ms.train").read({**data, "kind": "serve"}) is None
    assert _reader("attn_ms.train").read(
        {"kind": "train", "trace": trace}) is None


def test_device_scopes_breakdown(trace):
    rows = scopes.device_scopes(trace, HLO, scopes.TRAIN_MODULE)
    assert rows == [["mlp", pytest.approx(4.0)], ["attn", pytest.approx(2.0)],
                    ["head", pytest.approx(1.0)],
                    ["attn+mlp", pytest.approx(0.5)],
                    [scopes.UNSCOPED, pytest.approx(2.5)]]
    assert scopes.device_scopes(trace, HLO, scopes.TRAIN_MODULE, k=1) == [
        ["mlp", pytest.approx(4.0)], [scopes.UNSCOPED, pytest.approx(2.5)]]


def test_a_program_without_layer_scopes_reads_nothing(trace, monkeypatch):
    """Laid over a program older than ``repro.obs``, the readers give None
    and nothing raises."""
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert scopes.program_obs() is None
    text = HLO.replace("HloModule jit_step", "HloModule jit_step_older")
    data = {"kind": "train", "trace": trace, "hlo_text": text}
    assert {_reader(m).read(data) for m in scopes.METRICS} == {None}
    assert set(scopes.buckets(text).values()) == {scopes.UNSCOPED}
    assert scopes.device_scopes(trace, text, scopes.TRAIN_MODULE) == [
        [scopes.UNSCOPED, pytest.approx(10.0)]]


# ---------------------------------------------------------------------------
# the scopes cover the compiled steps
# ---------------------------------------------------------------------------

KEY = jax.random.PRNGKey(0)
TRAIN_SCOPES = {obs.ATTN, obs.MLP, obs.HEAD, obs.LOSS, obs.EMBED,
                obs.OPTIMIZER, obs.RECURRENT}


def _tiny(arch):
    from repro.configs import get_config, reduce_config
    cfg = reduce_config(get_config(arch))
    # two KV heads, as the benchmark's configuration has several: with one,
    # XLA's batch-dot simplification rebuilds the attention dots without
    # their op_name
    if cfg.block_kind(0) == "attn":
        cfg = dataclasses.replace(cfg, n_kv_heads=2)
    return cfg


def _by_scope(text):
    """{opcode class: Counter of (layer scopes on the path, transposed)}."""
    from repro.roofline.hlo_parse import HloModule
    out = collections.defaultdict(collections.Counter)
    for ops in HloModule(text).comps.values():
        for op in ops:
            kind = "dot" if op.opcode in ("dot", "convolution") else "other"
            out[kind][(tuple(scopes.layer_scopes(op.scope)),
                       "transpose(" in op.scope)] += 1
    return out


@pytest.mark.parametrize("arch,remat,mixer", [
    ("mistral-nemo-12b", False, obs.ATTN), ("mistral-nemo-12b", True, obs.ATTN),
    ("rwkv6-7b", True, obs.RECURRENT)])
def test_train_step_matmuls_lie_under_one_layer_scope(arch, remat, mixer):
    from repro.core import lora as lora_lib
    from repro.models import transformer as tfm
    from repro.models.transformer import ExecConfig
    from repro.optim import adamw
    from repro.train.steps import TrainHParams, make_train_step
    cfg = _tiny(arch)
    params = tfm.init_params(cfg, KEY)
    lora = lora_lib.init_lora_params(cfg, jax.random.fold_in(KEY, 1))
    toks = jax.random.randint(KEY, (2, 17), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    step = jax.jit(make_train_step(cfg, ExecConfig(remat=remat),
                                   TrainHParams()))
    got = _by_scope(step.lower(params, lora, adamw.init(lora), batch,
                               KEY).compile().as_text())
    for (found, _), _n in got["dot"].items():
        assert len(found) == 1 and found[0] in TRAIN_SCOPES, found
    dots = {(s[0], t) for (s, t) in got["dot"]}
    for scope in (mixer, obs.MLP, obs.HEAD):
        assert (scope, False) in dots and (scope, True) in dots, scope
    other = {(s[-1], t) for (s, t) in got["other"] if s}
    assert (obs.LOSS, False) in other and (obs.LOSS, True) in other
    assert (obs.OPTIMIZER, False) in other and (obs.EMBED, False) in other


def test_serving_mixed_step_matmuls_lie_under_one_layer_scope():
    from repro.core import lora as lora_lib
    from repro.models import transformer as tfm
    from repro.serve.engine import PagedServeEngine
    cfg = _tiny("mistral-nemo-12b")
    params = tfm.init_params(cfg, KEY)
    lora = lora_lib.init_lora_params(cfg, jax.random.fold_in(KEY, 1))
    eng = PagedServeEngine(cfg, params, adapters=[lora], max_slots=2,
                           max_len=32, page_size=4, prefill_chunk=8)
    B, C, nb = 2, 8, 4

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)
    text = eng._step.lower(eng.params, eng.adapters, eng.cache, i32(B, C),
                           i32(B), i32(B), i32(B, nb), i32(B), KEY,
                           jnp.zeros((B,), jnp.float32)).compile().as_text()
    got = _by_scope(text)
    for (found, _), _n in got["dot"].items():
        assert len(found) == 1 and found[0] in (obs.ATTN, obs.MLP,
                                                obs.HEAD), found
    assert {s[0] for s, _ in got["dot"]} == {obs.ATTN, obs.MLP, obs.HEAD}


def test_profiler_op_names_are_the_compiled_texts_instructions(tmp_path):
    """The join key: the instruction names a profiler trace records
    (``hlo_op`` on the CPU; the TPU's event names start with them) are
    those of the compiled executable's text."""
    from jax.profiler import ProfileData

    def f(w, x):
        def body(x, wi):
            with jax.named_scope(obs.ATTN):
                x = jnp.tanh(x @ wi)
            with jax.named_scope(obs.MLP):
                return x + jnp.sin(x @ wi.T), None
        return jax.lax.scan(body, x, w)[0].sum()

    step = jax.jit(jax.grad(f))
    w, x = jnp.full((3, 32, 32), 0.01), jnp.ones((32, 32))
    step(w, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(w, x).block_until_ready()
    jax.profiler.stop_trace()
    names = scopes.op_names(step.lower(w, x).compile().as_text())
    pd = ProfileData.from_file(glob.glob(f"{tmp_path}/**/*.xplane.pb",
                                         recursive=True)[0])
    traced = {dict(e.stats)["hlo_op"] for p in pd.planes for ln in p.lines
              for e in ln.events
              if dict(e.stats).get("hlo_module") == "jit_f"}
    assert traced and traced <= set(names)
    assert {scopes.scope_of(names[op]) for op in traced} >= {obs.ATTN,
                                                             obs.MLP}


# ---------------------------------------------------------------------------
# the script's traced run, at a tiny size on the CPU
# ---------------------------------------------------------------------------

CONFIG = {
    "name": "tiny-dense", "source": "test", "family": "dense",
    "program_arch": "mistral-nemo-12b", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 257,
    "rms_norm_eps": 1e-05, "rope_theta": 1e6, "tie_word_embeddings": False,
    "lora": {"rank": 4, "alpha": 4, "targets": ["wq", "wv"], "adapters": 3},
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3}}
SFT = {
    "kind": "train", "seq_len": 32, "tokens_per_step": 128,
    "microbatch_rows": 4, "remat": True,
    "adamw": {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
              "grad_clip": 1.0},
    "check_steps": 3, "trace": {"start_fraction": 0.3, "steps": 2}}


def test_traced_run_fetches_the_steps_text(tmp_path):
    from bench.harness import Run
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs" / "tiny-dense.json").write_text(json.dumps(CONFIG))
    (root / "traffic" / "tiny-sft.json").write_text(json.dumps(SFT))
    manifest = {"workloads": [
        {"name": "train", "config": "tiny-dense", "traffic": "tiny-sft",
         "chips": 1, "why": "t"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = load_cell("train", tmp_path / "BENCHMARK.json", root)
    line = scopes.traced(Run(cell=cell, seed=2 ** 31 + 5, seconds=1.0,
                             trace=True, t_start=time.perf_counter(),
                             peaks={"bf16_flops_per_s": 1e12},
                             out_dir=tmp_path / "out"))
    # the harness's own line, with the scopes added
    assert line["correct"] and line["attempted"] > 0
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    assert line["scopes"]["hlo_fetch_s"] >= 0
    # the CPU has no device plane: nothing to read, and no fault
    assert line["metrics"] == {}
    assert "device_scopes" not in line.get("breakdown", {})
    found = {b for k in line["scopes"]["instructions"] for b in k.split("+")}
    assert {obs.ATTN, obs.MLP, obs.HEAD, obs.LOSS, obs.OPTIMIZER} <= found


@pytest.fixture
def metadata_key():
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    yield
    jax.config.update(flag, was)


def test_script_goes_through_run_py_and_refuses_the_cpu(capsys,
                                                         metadata_key):
    from bench import harness
    plain = harness.run_cell
    assert scopes.main(["--workload", "nemo-lora-train", "--seed", "1",
                        "--seconds", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "accelerator chip" in err
    assert harness.run_cell is plain

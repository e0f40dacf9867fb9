"""The Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed with JAX and compiles for
a described (not attached) ``v5e:2x2`` topology. Interpret-mode tests
(test_kernels.py) check the kernels' numbers; only this file shows that
Mosaic accepts their block shapes, layouts and memory spaces, and that the
compiled program really holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.configs import get_config, reduce_config
from repro.core import lora as lora_lib
from repro.core.quant import quantize
from repro.kernels.crossbar_matmul import ops as cb_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.kernels.selective_scan import ops as ss_ops
from repro.models import attention as attn
from repro.models import ssm
from repro.models import transformer as tfm
from repro.optim import adamw
from repro.train.steps import TrainHParams, make_train_step

FUSED_KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
SCAN_KERNELS = ("selective_scan_fwd", "selective_scan_bwd")


@pytest.fixture(scope="module")
def topo():
    # a CPU-only JAX install has no TPU compiler; where it is installed, a
    # failure to describe the topology is a failure, not a skip
    pytest.importorskip("libtpu", reason="the TPU compiler is not installed")
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library otherwise writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on the described chip, with the persistent compile cache
    off: an entry compiled for a described chip cannot be read back
    without one, and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_text(fn, args, sharding) -> str:
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles_llama3_2_1b_prefill(one_chip, dtype):
    """llama3.2-1b prefill: 32 query / 8 kv heads, T = S = 2048, D = 64."""
    B, T, Hq, Hkv, D = 1, 2048, 32, 8, 64
    args = (jax.ShapeDtypeStruct((B, T, Hq, D), dtype),
            jax.ShapeDtypeStruct((B, T, Hkv, D), dtype),
            jax.ShapeDtypeStruct((B, T, Hkv, D), dtype),
            jax.ShapeDtypeStruct((B, T), jnp.int32),
            jax.ShapeDtypeStruct((B, T), jnp.int32))
    txt = _compile_text(
        lambda q, k, v, qp, kp: fa_ops.flash_attention(q, k, v, qp, kp),
        args, one_chip)
    assert "tpu_custom_call" in txt


def _kernel_calls(txt):
    """{instruction name: op_name} of every Pallas kernel in compiled text."""
    calls = {}
    for line in txt.splitlines():
        m = re.match(r"\s*%?([\w.-]+) = .*custom_call_target=\"tpu_custom_call\"",
                     line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            calls[m.group(1)] = op.group(1) if op else ""
    return calls


def _innermost_layer(op_name):
    """The innermost layer scope on an op_name path, with the autodiff
    wrappers (``jvp(...)``, ``transpose(...)``) taken off each part."""
    found = None
    for part in op_name.split("/"):
        while (m := re.match(r"^(?:transpose|jvp|vmap)\((.*)\)$", part)):
            part = m.group(1)
        if part in obs.LAYERS:
            found = part
    return found


@pytest.mark.parametrize("B,Hq,Hkv,D", [
    (8, 32, 8, 128),     # mistral-nemo-12b, the benchmark's batch
    (1, 64, 8, 128),     # chameleon-34b, jamba-1.5-large: G = 8
    (1, 48, 8, 128),     # mixtral-8x22b, internlm2-20b: G = 6
    (1, 32, 8, 64),      # llama3.2-1b: D = 64
])
def test_fused_attention_compiles_nemo_train_shapes(one_chip, B, Hq, Hkv, D):
    """Training attention through ``attend``, T = S = 1024, float32, value
    and gradient, at mistral-nemo-12b's heads and at the other head ratios
    and widths the kernels take. The three kernels are there by name,
    within VMEM, with no conditional left from the platform choice and no
    (..., 1024, 512) or (..., 1024, 1024) float32 score array."""
    T = 1024
    args = (jax.ShapeDtypeStruct((B, T, Hq, D), jnp.float32),
            jax.ShapeDtypeStruct((B, T, Hkv, D), jnp.float32),
            jax.ShapeDtypeStruct((B, T, Hkv, D), jnp.float32),
            jax.ShapeDtypeStruct((B, T), jnp.int32))

    def f(q, k, v, pos):
        def value(q, k, v):
            return jnp.sum(attn.attend(
                q, k, v, pos, pos, kind="full", window=None, softcap=None,
                impl="auto", block_q=512, block_kv=512) ** 2)
        return jax.value_and_grad(value, argnums=(0, 1, 2))(q, k, v)

    txt = _compile_text(f, args, one_chip)
    names = {n.split(".")[0] for n in _kernel_calls(txt)}
    assert names == set(FUSED_KERNELS)
    assert " conditional(" not in txt
    assert not re.search(r"f32\[(\d+,)*1024,(512|1024)\]", txt)


def test_fused_attention_kernels_sit_under_attn_scope(one_chip):
    """A one-layer train step at small width, compiled for the v5e, where
    it takes the fused route: each kernel's op_name lies under the ``attn``
    layer scope, so the device trace counts it as attention."""
    cfg = dataclasses.replace(reduce_config(get_config("mistral-nemo-12b")),
                              n_layers=1)
    B, T = 2, 256
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: tfm.init_params(cfg, k), key)
    lora = jax.eval_shape(lambda k: lora_lib.init_lora_params(cfg, k), key)
    batch = {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
    step = make_train_step(cfg, tfm.ExecConfig(block_q=128, block_kv=128),
                           TrainHParams())
    txt = _compile_text(step, (params, lora, jax.eval_shape(adamw.init, lora),
                               batch, key), one_chip)
    calls = _kernel_calls(txt)
    assert {n.split(".")[0] for n in calls} == set(FUSED_KERNELS)
    for name, op_name in calls.items():
        assert _innermost_layer(op_name) == obs.ATTN, (name, op_name)


@pytest.fixture(scope="module")
def jamba_step_text(one_chip):
    """One period of jamba2-3b at small width, remat on, one row of 256
    tokens, its training step compiled for the v5e."""
    cfg = dataclasses.replace(reduce_config(get_config("jamba2-3b")),
                              n_layers=14)
    assert cfg.n_kv_heads == 1
    B, T = 1, 256
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: tfm.init_params(cfg, k), key)
    lora = jax.eval_shape(lambda k: lora_lib.init_lora_params(cfg, k), key)
    batch = {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
    step = make_train_step(cfg, tfm.ExecConfig(remat=True), TrainHParams())
    return _compile_text(step, (params, lora,
                                jax.eval_shape(adamw.init, lora), batch, key),
                         one_chip)


def test_jamba_train_step_keeps_its_layer_scopes(jamba_step_text):
    """With one KV head the attention's score and value products keep
    their op_name under ``attn`` (the CPU compiler drops it), every matmul
    lies under one layer scope, and the selective scan shows under
    ``ssm_scan`` inside ``recurrent``."""
    txt = jamba_step_text
    dots, scan = set(), 0
    for line in txt.splitlines():
        op = re.search(r'op_name="([^"]*)"', line)
        if op is None:
            continue
        if re.match(r"\s*(ROOT )?%?[\w.-]+ = \S+ (dot|convolution)\(", line):
            dots.add((_innermost_layer(op.group(1)),
                      "->" in op.group(1).rsplit("/", 2)[-2]))
        scan += "/recurrent/ssm_scan/" in op.group(1)
    assert None not in {layer for layer, _ in dots}, dots
    # the einsums of the attention core (``bthgd,bshd->bhgts`` and back)
    assert (obs.ATTN, True) in dots
    assert {obs.ATTN, obs.RECURRENT, obs.MLP, obs.HEAD} <= {
        layer for layer, _ in dots}
    assert scan > 0


def test_selective_scan_kernels_sit_under_ssm_scan_scope(jamba_step_text):
    """The jamba step's scans (T = 256, unsharded) run as the fused kernels,
    forward and backward, each under the ``ssm_scan`` layer scope, so the
    device trace counts them with the scan, and no conditional is left
    from the platform choice."""
    calls = _kernel_calls(jamba_step_text)
    scans = {n: op for n, op in calls.items()
             if n.split(".")[0] in SCAN_KERNELS}
    assert {n.split(".")[0] for n in scans} == set(SCAN_KERNELS)
    for name, op_name in scans.items():
        assert _innermost_layer(op_name) == obs.SSM_SCAN, (name, op_name)
    assert " conditional(" not in jamba_step_text


def test_selective_scan_kernels_compile_jamba2_3b(one_chip):
    """jamba2-3b's widths, one row of 8192 tokens: d_in 5120, d_state 16.
    Forward and backward compile within VMEM, each kernel by name."""
    B, T, D, N = 1, 8192, 5120, 16
    seq = jax.ShapeDtypeStruct((B, T, D), jnp.float32)
    bc = jax.ShapeDtypeStruct((B, T, N), jnp.float32)
    A = jax.ShapeDtypeStruct((D, N), jnp.float32)
    h = jax.ShapeDtypeStruct((B, D, N), jnp.float32)
    hs = jax.ShapeDtypeStruct(ss_ops.block_states_shape((B, T, D), N),
                              jnp.float32)
    fwd = _compile_text(ss_ops.scan_fwd, (seq, bc, bc, seq, A, h), one_chip)
    bwd = _compile_text(ss_ops.scan_bwd, (seq, bc, bc, seq, A, hs, seq, h),
                        one_chip)
    assert {n.split(".")[0] for n in _kernel_calls(fwd)} == {SCAN_KERNELS[0]}
    assert {n.split(".")[0] for n in _kernel_calls(bwd)} == {SCAN_KERNELS[1]}


@pytest.mark.parametrize("T", [1, 16])
def test_decode_length_scan_keeps_the_xla_path(one_chip, T):
    """A decode step or a short chunk of the Mamba block, with its cache,
    at jamba2-3b's widths: the chunked scan in XLA, no kernel."""
    cfg = get_config("jamba2-3b")
    d_in, N = cfg.mamba.expand * cfg.d_model, cfg.mamba.d_state
    assert not ss_ops.applies(T)
    key = jax.random.PRNGKey(0)
    p = jax.eval_shape(lambda k: ssm.init_mamba(cfg, k, jnp.bfloat16), key)
    cache = {"conv": jax.ShapeDtypeStruct((2, cfg.mamba.d_conv - 1, d_in),
                                          jnp.bfloat16),
             "ssm": jax.ShapeDtypeStruct((2, d_in, N), jnp.float32)}
    x = jax.ShapeDtypeStruct((2, T, cfg.d_model), jnp.bfloat16)
    txt = _compile_text(
        lambda p, x, c: ssm.apply_mamba_block(cfg, p, x, cache=c),
        (p, x, cache), one_chip)
    assert not _kernel_calls(txt)


def test_rwkv6_wkv_compiles_rwkv6_7b(one_chip):
    """rwkv6-7b: 64 heads of N = 64, T = 1024."""
    B, T, H, N = 1, 1024, 64, 64
    seq = jax.ShapeDtypeStruct((B, T, H, N), jnp.float32)
    args = (seq, seq, seq, seq, jax.ShapeDtypeStruct((H, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32))
    txt = _compile_text(wkv_ops.rwkv6_wkv, args, one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("bits", [8, 4])
def test_crossbar_matmul_compiles_2048x8192(one_chip, bits):
    """A 2048 x 8192 crossbar-quantized weight under 256 activation rows."""
    K, N, M = 2048, 8192, 256
    qt = jax.eval_shape(lambda w: quantize(w, bits),
                        jax.ShapeDtypeStruct((K, N), jnp.float32))
    x = jax.ShapeDtypeStruct((M, K), jnp.float32)
    txt = _compile_text(cb_ops.crossbar_matmul, (x, qt), one_chip)
    assert "tpu_custom_call" in txt

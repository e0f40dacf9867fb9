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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import quantize
from repro.kernels.crossbar_matmul import ops as cb_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops


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

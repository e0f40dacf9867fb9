"""Kernel parity: each Pallas kernel in interpret mode vs its jnp reference.

The kernels run with ``interpret=True`` on whatever platform this process
has, so a row's time is the Pallas interpreter's, never a kernel time:
every row names its platform and says "interpret". Kernel times come only
from a profiler trace on the chip."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json, timed
from repro.core.quant import quantize
from repro.kernels.crossbar_matmul import ops as cb_ops, ref as cb_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.models.attention import blocked_attention, ref_attention
from repro.models.rwkv import wkv_scan

KEY = jax.random.PRNGKey(0)


def run():
    plat = jax.devices()[0].platform
    payload = {"platform": plat, "mode": "interpret"}
    # crossbar matmul
    w = jax.random.normal(KEY, (512, 256)) * 0.1
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 512))
    for bits in (8, 4):
        qt = quantize(w, bits)
        y, us = timed(lambda: cb_ops.crossbar_matmul(x, qt, block_m=64,
                                                     interpret=True)
                      .block_until_ready())
        yr = cb_ref.crossbar_matmul_ref(x, qt)
        err = float(jnp.max(jnp.abs(y - yr)))
        payload[f"crossbar_int{bits}"] = {"interpret_us": us, "err": err}
        emit(f"interpret_crossbar_int{bits}", us,
             f"platform={plat}_interpret_mode_err={err:.2e}")

    # flash attention
    q = jax.random.normal(KEY, (2, 128, 4, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 128, 2, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 128, 2, 32))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    o, us = timed(lambda: fa_ops.flash_attention(q, k, v, pos, pos,
                                                 block_q=64, block_kv=64,
                                                 interpret=True)
                  .block_until_ready())
    oref = ref_attention(q, k, v, pos, pos)
    err = float(jnp.max(jnp.abs(o - oref)))
    payload["flash_attention"] = {"interpret_us": us, "err": err}
    emit("interpret_flash_attention", us,
         f"platform={plat}_interpret_mode_err={err:.2e}")
    _, us_jnp = timed(lambda: blocked_attention(q, k, v, pos, pos,
                                                block_kv=64)
                      .block_until_ready())
    emit("jnp_blocked_attention", us_jnp, f"platform={plat}_reference_path")

    # rwkv wkv
    r = jax.random.normal(KEY, (1, 128, 4, 32))
    kk = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 128, 4, 32))
    vv = jax.random.normal(jax.random.fold_in(KEY, 5), (1, 128, 4, 32))
    ww = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(KEY, 6),
                                          (1, 128, 4, 32)))
    u = jax.random.normal(jax.random.fold_in(KEY, 7), (4, 32)) * 0.3
    s0 = jnp.zeros((1, 4, 32, 32))
    (yk, sk), us = timed(lambda: jax.tree.map(
        lambda a: a.block_until_ready(),
        wkv_ops.rwkv6_wkv(r, kk, vv, ww, u, s0, block_t=64, interpret=True)))
    yref, sref = wkv_scan(r, kk, vv, ww, u, s0)
    err = float(jnp.max(jnp.abs(yk - yref)))
    payload["rwkv6_wkv"] = {"interpret_us": us, "err": err}
    emit("interpret_rwkv6_wkv", us,
         f"platform={plat}_interpret_mode_err={err:.2e}")
    save_json("kernel_micro", payload)
    return payload


if __name__ == "__main__":
    run()

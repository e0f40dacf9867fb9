"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles. The kernels run
in Pallas interpret mode here (``interpret=True``): the CPU cannot run a
Mosaic kernel; tests/test_tpu_compile.py compiles them for the TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import quantize
from repro.kernels.crossbar_matmul import ops as cb_ops, ref as cb_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.models.attention import ref_attention
from repro.models.rwkv import wkv_scan

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("mkn", [(32, 128, 128), (64, 256, 384),
                                 (100, 300, 130), (8, 520, 250)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_crossbar_matmul_sweep(bits, mkn, dtype):
    M, K, N = mkn
    kw, kx = jax.random.split(jax.random.fold_in(KEY, M * K * N + bits))
    w = jax.random.normal(kw, (K, N), jnp.float32) * 0.1
    x = (jax.random.normal(kx, (M, K), jnp.float32)).astype(dtype)
    qt = quantize(w, bits)
    y = cb_ops.crossbar_matmul(x, qt, block_m=32, out_dtype=jnp.float32,
                               interpret=True)
    yr = cb_ref.crossbar_matmul_ref(x.astype(jnp.float32), qt,
                                    out_dtype=jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=tol, atol=tol * float(jnp.max(jnp.abs(yr))))


def test_crossbar_batched_lead_dims():
    w = jax.random.normal(KEY, (256, 128)) * 0.1
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 5, 256))
    qt = quantize(w, 8)
    y = cb_ops.crossbar_matmul(x, qt, block_m=32, interpret=True)
    assert y.shape == (2, 5, 128)
    yr = cb_ref.crossbar_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("B,T,S,Hq,Hkv,D", [
    (2, 64, 64, 4, 2, 16), (1, 32, 96, 4, 4, 8), (2, 64, 64, 8, 2, 32),
    (1, 1, 64, 4, 2, 16), (1, 48, 48, 6, 3, 64),
])
@pytest.mark.parametrize("window,softcap", [(None, None), (16, None),
                                            (None, 20.0)])
def test_flash_attention_sweep(B, T, S, Hq, Hkv, D, window, softcap):
    ks = jax.random.split(jax.random.fold_in(KEY, T * S * Hq + D), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    qpos = jnp.broadcast_to(jnp.arange(S - T, S)[None], (B, T))
    kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    o_ref = ref_attention(q, k, v, qpos, kpos, window=window, softcap=softcap)
    o_ker = fa_ops.flash_attention(q, k, v, qpos, kpos, window=window,
                                   softcap=softcap, block_q=16, block_kv=16,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_invalid_slots_masked():
    """kv_pos == -1 (unwritten ring slots) must contribute nothing."""
    B, T, S, H, D = 1, 8, 32, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    qpos = jnp.broadcast_to(jnp.arange(T)[None] + 100, (B, T))
    kpos = jnp.where(jnp.arange(S) < 20, jnp.arange(S) + 90, -1)[None]
    o1 = fa_ops.flash_attention(q, k, v, qpos, kpos, block_q=8, block_kv=8,
                                interpret=True)
    # corrupt the invalid region: output must not change
    k2 = k.at[:, 20:].set(999.0)
    v2 = v.at[:, 20:].set(-999.0)
    o2 = fa_ops.flash_attention(q, k2, v2, qpos, kpos, block_q=8, block_kv=8,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


@pytest.mark.parametrize("B,T,H,N,bt", [(2, 96, 4, 16, 32), (1, 64, 2, 32, 64),
                                        (1, 50, 3, 8, 16)])
def test_rwkv6_wkv_sweep(B, T, H, N, bt):
    ks = jax.random.split(jax.random.fold_in(KEY, B * T * H * N), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, N))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    s0 = jax.random.normal(jax.random.fold_in(KEY, 9), (B, H, N, N)) * 0.1
    y_ref, s_ref = wkv_scan(r, k, v, w, u, s0)
    y_k, s_k = wkv_ops.rwkv6_wkv(r, k, v, w, u, s0, block_t=bt,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), rtol=1e-5,
                               atol=1e-5)

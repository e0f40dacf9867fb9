"""Mamba & RWKV blocks: chunked-scan correctness, decode/prefill state
continuity, hypothesis invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # bare container — CI installs the real thing
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config, reduce_config
from repro.core import hetero
from repro.kernels.selective_scan import ops as ss_ops
from repro.models import rwkv as rwkv_mod, ssm

KEY = jax.random.PRNGKey(2)


def _step_scan_oracle(dt, Bc, Cc, xi, A, h0):
    """Per-step sequential oracle for the selective scan."""
    B, T, D = dt.shape
    h = h0
    ys = []
    for t in range(T):
        a = jnp.exp(dt[:, t, :, None] * A)
        bx = (dt[:, t] * xi[:, t])[..., None] * Bc[:, t][:, None, :]
        h = a * h + bx
        ys.append(jnp.einsum("bdn,bn->bd", h, Cc[:, t]))
    return jnp.stack(ys, 1), h


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_selective_scan_chunk_invariance(chunk):
    B, T, D, N = 2, 50, 8, 4
    ks = jax.random.split(KEY, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, D)))
    Bc = jax.random.normal(ks[1], (B, T, N))
    Cc = jax.random.normal(ks[2], (B, T, N))
    xi = jax.random.normal(ks[3], (B, T, D))
    A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.3)
    h0 = jnp.zeros((B, D, N))
    y_ref, h_ref = _step_scan_oracle(dt, Bc, Cc, xi, A, h0)
    y, h = ssm._selective_scan(dt, Bc, Cc, xi, A, h0, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-4,
                               atol=1e-4)


def test_mamba_block_decode_continuation():
    cfg = reduce_config(get_config("jamba-1.5-large-398b"))
    p = ssm.init_mamba(cfg, KEY, jnp.float32)
    B, T = 2, 12
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, cfg.d_model))
    y_full, _ = ssm.apply_mamba_block(cfg, p, x)
    # prefill 8 + decode 4 must match
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    cache = {"conv": jnp.zeros((B, mc.d_conv - 1, d_in)),
             "ssm": jnp.zeros((B, d_in, mc.d_state))}
    y_pf, cache = ssm.apply_mamba_block(cfg, p, x[:, :8], cache=cache)
    ys = [y_pf]
    for t in range(8, T):
        y_t, cache = ssm.apply_mamba_block(cfg, p, x[:, t:t + 1], cache=cache)
        ys.append(y_t)
    y_inc = jnp.concatenate(ys, 1)
    np.testing.assert_allclose(np.asarray(y_inc), np.asarray(y_full),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_mamba_block_tally_is_the_same_on_either_scan_path(grad):
    """One Mamba block over two kernel time blocks: unsharded it stages the
    fused kernels, with an (identity) sharder the chunked scan alone; the
    ``hetero`` tally reads the same either way, forward and gradient."""
    cfg = reduce_config(get_config("jamba2-3b"))
    p = ssm.init_mamba(cfg, KEY, jnp.float32)
    x = jax.random.normal(KEY, (1, 2 * ss_ops.BLOCK_T, cfg.d_model))

    def traced(sharder):
        def f(x):
            return jnp.sum(ssm.apply_mamba_block(cfg, p, x,
                                                 sharder=sharder)[0])
        fn = jax.grad(f) if grad else f
        with hetero.tally() as counts:
            text = str(jax.make_jaxpr(fn)(x))
        return counts, text

    fused, fused_text = traced(None)
    chunked, chunked_text = traced(lambda a, name: a)
    assert "selective_scan_fwd" in fused_text
    assert "selective_scan_bwd" in fused_text or not grad
    assert "pallas_call" not in chunked_text
    assert fused == chunked and fused[hetero.DYNAMIC] > 0


@pytest.mark.parametrize("chunk", [8, 32, 128])
def test_wkv_chunk_invariance(chunk):
    B, T, H, N = 1, 40, 2, 8
    ks = jax.random.split(KEY, 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, N)))
    u = jax.random.normal(ks[4], (H, N)) * 0.2
    s0 = jnp.zeros((B, H, N, N))
    y1, s1 = rwkv_mod.wkv_scan(r, k, v, w, u, s0, chunk=chunk)
    y2, s2 = rwkv_mod.wkv_scan(r, k, v, w, u, s0, chunk=1024)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-5)


def test_wkv_grads_through_chunked_checkpoint():
    B, T, H, N = 1, 32, 2, 4
    ks = jax.random.split(KEY, 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, N)))
    u = jax.random.normal(ks[4], (H, N)) * 0.2
    s0 = jnp.zeros((B, H, N, N))

    def loss(r, k, v, w, chunk):
        y, s = rwkv_mod.wkv_scan(r, k, v, w, u, s0, chunk=chunk)
        return jnp.sum(y ** 2) + jnp.sum(s ** 2)

    g8 = jax.grad(loss, (0, 1, 2, 3))(r, k, v, w, 8)
    gfull = jax.grad(loss, (0, 1, 2, 3))(r, k, v, w, 1024)
    for a, b in zip(g8, gfull):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_rwkv_block_decode_continuation():
    cfg = reduce_config(get_config("rwkv6-7b"))
    p = rwkv_mod.init_rwkv(cfg, KEY, jnp.float32)
    B, T = 1, 10
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, cfg.d_model))
    y_full, _ = rwkv_mod.apply_rwkv_block(cfg, p, x)
    H = cfg.d_model // cfg.rwkv.head_dim
    cache = {"shift_t": jnp.zeros((B, cfg.d_model)),
             "shift_c": jnp.zeros((B, cfg.d_model)),
             "wkv": jnp.zeros((B, H, cfg.rwkv.head_dim, cfg.rwkv.head_dim))}
    ys = []
    for t in range(T):
        y_t, cache = rwkv_mod.apply_rwkv_block(cfg, p, x[:, t:t + 1],
                                               cache=cache)
        ys.append(y_t)
    y_inc = jnp.concatenate(ys, 1)
    np.testing.assert_allclose(np.asarray(y_inc), np.asarray(y_full),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_wkv_decay_contracts_state(seed):
    """With k=0 the state must contract monotonically (w in (0,1))."""
    B, T, H, N = 1, 16, 1, 4
    key = jax.random.PRNGKey(seed)
    r = jnp.zeros((B, T, H, N))
    k = jnp.zeros((B, T, H, N))
    v = jnp.zeros((B, T, H, N))
    w = jax.nn.sigmoid(jax.random.normal(key, (B, T, H, N))) * 0.98 + 0.01
    u = jnp.zeros((H, N))
    s0 = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, H, N, N)))
    _, s_fin = rwkv_mod.wkv_scan(r, k, v, w, u, s0)
    assert bool(jnp.all(jnp.abs(s_fin) <= jnp.abs(s0) + 1e-6))

"""Flash/banded attention vs reference, including custom-VJP gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn

KEY = jax.random.PRNGKey(3)


def _qkv(B, T, S, Hq, Hkv, D, salt=0):
    ks = jax.random.split(jax.random.fold_in(KEY, salt), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    qp = jnp.broadcast_to(jnp.arange(S - T, S)[None], (B, T))
    kp = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return q, k, v, qp, kp


@pytest.mark.parametrize("window,softcap", [(None, None), (24, None),
                                            (None, 30.0), (24, 10.0)])
def test_flash_custom_vjp_matches_ref_grads(window, softcap):
    q, k, v, qp, kp = _qkv(2, 64, 64, 4, 2, 16)

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v, qp, kp, window=window, softcap=softcap)
            return jnp.sum(o * (o + 0.5))
        return f

    g_ref = jax.grad(loss(attn.ref_attention), argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss(lambda *a, **kw: attn.blocked_attention(
        *a, block_kv=16, **kw)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_banded_equals_ref_sliding():
    B, T, Hq, Hkv, D, W = 1, 128, 4, 2, 16, 32
    q, k, v, qp, kp = _qkv(B, T, T, Hq, Hkv, D, salt=5)
    o_ref = attn.ref_attention(q, k, v, qp, kp, window=W)
    o_band = attn.banded_attention(q, k, v, qp, kp, window=W, block_q=32,
                                   block_kv=16)
    np.testing.assert_allclose(np.asarray(o_band), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)


def test_banded_grads():
    B, T, Hq, Hkv, D, W = 1, 64, 2, 2, 8, 16
    q, k, v, qp, kp = _qkv(B, T, T, Hq, Hkv, D, salt=6)

    def f_ref(q, k, v):
        return jnp.sum(attn.ref_attention(q, k, v, qp, kp, window=W) ** 2)

    def f_band(q, k, v):
        return jnp.sum(attn.banded_attention(q, k, v, qp, kp, window=W,
                                             block_q=16, block_kv=16) ** 2)

    g1 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_band, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_window_ge_seq_degenerates_to_full():
    q, k, v, qp, kp = _qkv(1, 32, 32, 2, 2, 8, salt=7)
    o_full = attn.attend(q, k, v, qp, kp, kind="full", window=None,
                         softcap=None, impl="auto", block_q=16, block_kv=16)
    o_win = attn.attend(q, k, v, qp, kp, kind="sliding", window=64,
                        softcap=None, impl="auto", block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(o_full), np.asarray(o_win),
                               rtol=1e-5, atol=1e-5)


def test_ring_cache_decode_matches_full_history():
    """Sliding decode with a ring buffer must equal attention over the last
    W tokens of the true history."""
    from repro.configs import get_config, reduce_config
    from repro.models.kvcache import init_cache
    from repro.models import transformer as tfm

    cfg = reduce_config(get_config("mixtral-8x22b"))   # sliding window 8
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    B, T = 1, 24
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
    ec = tfm.ExecConfig(capacity_factor=16.0)
    full, _, _ = tfm.forward(cfg, params, {"tokens": toks}, mode="train",
                             exec_cfg=ec)
    cache = init_cache(cfg, B, T, kv_dtype=jnp.float32)
    _, cache, _ = tfm.forward(cfg, params, {"tokens": toks[:, :8]},
                              mode="prefill", prefill_cache_len=T,
                              cache=cache, exec_cfg=ec)
    errs = []
    for t in range(8, T):
        lg, cache, _ = tfm.forward(cfg, params, {"tokens": toks[:, t:t + 1]},
                                   mode="decode", cache=cache, exec_cfg=ec)
        errs.append(float(jnp.max(jnp.abs(lg[:, -1] - full[:, t]))))
    assert max(errs) < 2e-4, errs


ROUTES = {"fused": ("_kernels_fwd",),
          "blocked": ("blocked_attention", "_flash_fwd_impl"),
          "banded": ("banded_attention",), "ref": ("ref_attention",)}


@pytest.mark.parametrize("case,platform,expect", [
    ("self", "cpu", "blocked"),
    ("self", "tpu", "fused"),
    ("softcap", "tpu", "blocked"),
    ("window", "tpu", "banded"),
    ("sharder", "tpu", "blocked"),
    ("decode", "tpu", "ref"),
    ("t_ne_s", "tpu", "blocked"),
    ("wide_groups", "tpu", "blocked"),
])
def test_attend_takes_fused_kernels_only_where_they_apply(monkeypatch, case,
                                                          platform, expect):
    """The fused kernels stand in for ``blocked`` only where the call is
    lowered for a TPU, for unsharded causal self-attention without softcap
    or window at a head ratio the kernels take; every other case keeps its
    path. Each route is replaced by one that returns its own constant, and
    the program lowered for ``platform`` holds the constant of the route
    taken, and no other. (``_kernels_fwd`` and ``_flash_fwd_impl`` are the
    two forwards of the self-attention route, with its log-sum-exp.)"""
    for i, fns in enumerate(ROUTES.values()):
        for fn in fns:
            def route(q, k, *a, _c=11.0 * (i + 1), _fn=fn, **kw):
                out = jnp.full(q.shape, _c, q.dtype)
                if _fn.startswith("_"):
                    B, T, Hq, _ = q.shape
                    Hkv = k.shape[2]
                    return out, jnp.zeros((B, Hkv, Hq // Hkv, T))
                return out
            monkeypatch.setattr(attn, fn, route)
    T, S = {"decode": (1, 256), "t_ne_s": (128, 256)}.get(case, (256, 256))
    Hq = 32 if case == "wide_groups" else 4
    q, k, v, qp, kp = _qkv(1, T, S, Hq, 2, 16)

    def f(q, k, v):
        return attn.attend(
            q, k, v, qp, kp, kind="sliding" if case == "window" else "full",
            window=64 if case == "window" else None,
            softcap=30.0 if case == "softcap" else None, impl="auto",
            block_q=64, block_kv=64,
            sharder=(lambda x, name: x) if case == "sharder" else None)

    txt = jax.jit(f).trace(q, k, v).lower(
        lowering_platforms=(platform,)).as_text()
    lowered = [name for i, name in enumerate(ROUTES)
               if f"dense<{11.0 * (i + 1):e}>" in txt]
    assert lowered == [expect]
    if platform == "cpu":
        np.testing.assert_array_equal(
            np.asarray(jax.jit(f)(q, k, v)),
            11.0 * (list(ROUTES).index(expect) + 1))


def test_fused_attention_tallies_like_blocked():
    """On the fused route the operation tally is the blocked path's, and
    that counts every KV block: 2 products of 2*B*T*Hq*S*D FLOPs forward,
    5 more backward."""
    from repro.core import hetero
    B, T, Hq, Hkv, D = 2, 256, 4, 2, 16
    q, k, v, qp, kp = _qkv(B, T, T, Hq, Hkv, D)

    def value_of(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, qp, kp))

    def grad_of(fn):
        return jax.grad(value_of(fn), argnums=(0, 1, 2))

    def fused_route(*a):
        return attn.attend(*a, kind="full", window=None, softcap=None,
                           impl="auto", block_q=64, block_kv=64)

    def blocked(*a):
        return attn.blocked_attention(*a, block_kv=64)

    for f, products in ((value_of, 2), (grad_of, 7)):
        rf = hetero.breakdown_of(f(fused_route), q, k, v)
        rb = hetero.breakdown_of(f(blocked), q, k, v)
        assert rf.dynamic_flops == rb.dynamic_flops == (
            products * 2.0 * B * T * Hq * T * D)
        assert rf.nonlinear_elems == rb.nonlinear_elems

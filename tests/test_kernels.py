"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles. The kernels run
in Pallas interpret mode here (``interpret=True``): the CPU cannot run a
Mosaic kernel; tests/test_tpu_compile.py compiles them for the TPU."""
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import quantize
from repro.kernels.crossbar_matmul import ops as cb_ops, ref as cb_ref
from repro.kernels.flash_attention import kernel as fa_kernel, ops as fa_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.kernels.selective_scan import ops as ss_ops, ref as ss_ref
from repro.models.attention import fused_attention, ref_attention
from repro.models.rwkv import wkv_scan
from repro.models.ssm import fused_scan

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.spec import BENCH, load_module  # noqa: E402

JAMBA = load_module(BENCH / "reference" / "jamba.py")
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


def _rounded_like_the_kernels(q, k, v, pos, kpos, dout):
    """Attention, its log-sum-exp (B*Hq, T) and (dq, dk, dv) for the
    cotangent ``dout``, each dot's operands rounded to bfloat16 where the
    kernels round them and float32 elsewhere. Exact only where each query
    row sees one kv block (no rescaling between blocks)."""
    bf, f32 = (lambda x: x.astype(jnp.bfloat16)), jnp.float32
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qs = q.reshape(B, T, Hkv, G, D) * D ** -0.5
    do = dout.reshape(B, T, Hkv, G, D)
    mask = ((kpos[:, None, :] <= pos[:, :, None])
            & (kpos[:, None, :] >= 0))[:, None, None]
    s = jnp.einsum("bthgd,bshd->bhgts", bf(qs), bf(k),
                   preferred_element_type=f32)
    s = jnp.where(mask, s, fa_kernel.NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum("bhgts,bshd->bthgd", bf(p), bf(v),
                   preferred_element_type=f32)
    o = o / l[..., 0].transpose(0, 3, 1, 2)[..., None]
    lse = m + jnp.log(l)
    pt = jnp.where(mask, jnp.exp(s - lse), 0.0)
    di = jnp.sum(do * o, -1).transpose(0, 2, 3, 1)[..., None]
    ds = pt * (jnp.einsum("bthgd,bshd->bhgts", bf(do), bf(v),
                          preferred_element_type=f32) - di)
    dq = jnp.einsum("bhgts,bshd->bthgd", bf(ds), bf(k),
                    preferred_element_type=f32) * D ** -0.5
    dk = jnp.einsum("bhgts,bthgd->bshd", bf(ds), bf(qs),
                    preferred_element_type=f32)
    dv = jnp.einsum("bhgts,bthgd->bshd", bf(pt), bf(do),
                    preferred_element_type=f32)
    return (o.reshape(B, T, Hq, D), lse.reshape(B * Hq, T),
            (dq.reshape(B, T, Hq, D), dk, dv))


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("B,T,Hq,Hkv,D,invalid,dots", [
    (2, 640, 8, 2, 64, None, None),        # GQA 4:1, five blocks of 128
    (1, 768, 2, 2, 128, None, None),       # GQA 1:1, three blocks of 256
    (2, 640, 4, 1, 64, (100, 150), None),  # invalid slots inside a block
    (1, 768, 4, 2, 128, (256, 512), None),  # a whole kv block invalid
    (1, 1024, 4, 1, 128, None, None),      # two blocks of 512
    (1, 1024, 8, 1, 64, None, None),       # GQA 8:1, q blocks of 256
    (1, 512, 4, 1, 128, None, "bf16"),     # the chip's bfloat16 dots
])
def test_fused_attention_forward_and_grads_match_ref(B, T, Hq, Hkv, D,
                                                     invalid, dots):
    """The fused kernels' output, log-sum-exp and dQ, dK, dV against
    ref_attention (the gradients through the custom VJP). Every case of
    several kv blocks has (q, kv) block pairs the mask hides entirely,
    which the kernels skip. With ``dots="bf16"`` the kernels cast each
    dot's operands to bfloat16 as compiled for the TPU, and are held to a
    reference that rounds at the same points, closely enough that a
    missing or misplaced cast fails."""
    ks = jax.random.split(jax.random.fold_in(KEY, T * Hq + D), 4)
    q = jax.random.normal(ks[0], (B, T, Hq, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    w = jax.random.normal(ks[3], (B, T, Hq, D))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    kpos = pos
    if invalid is not None:
        lo, hi = invalid
        kpos = jnp.where((pos >= lo) & (pos < hi), -1, pos)
    (bq, bk), _, _ = fa_ops.causal_blocks(T, Hq // Hkv, D)
    assert (T == bk or (fa_kernel.block_visibility(pos, kpos, bq, bk)
                        == fa_kernel.SKIP).any())

    if dots == "bf16":
        assert T == bk
        o, lse = fa_ops.causal_attention_fwd(q, k, v, pos, kpos,
                                             interpret=True,
                                             dot_dtype=jnp.bfloat16)
        grads = fa_ops.causal_attention_bwd(q, k, v, pos, kpos, o, lse, w,
                                            interpret=True,
                                            dot_dtype=jnp.bfloat16)
        o_ref, lse_ref, g_ref = _rounded_like_the_kernels(q, k, v, pos,
                                                          kpos, w)
        assert _rel_l2(o, o_ref) < 1e-5
        assert _rel_l2(lse.reshape(B * Hq, T), lse_ref) < 1e-6
        # relative L2 with the casts as compiled: output 7e-8, gradients
        # up to 7e-6; without them, or with q cast before its scale, 3e-3
        for a, b in zip(grads, g_ref):
            assert _rel_l2(a, b) < 1e-4
        return

    o, lse = fa_ops.causal_attention_fwd(q, k, v, pos, kpos, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(ref_attention(q, k, v, pos, kpos)),
        rtol=1e-5, atol=1e-5)
    qg = q.reshape(B, T, Hkv, Hq // Hkv, D) * D ** -0.5
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k,
                   precision=jax.lax.Precision.HIGHEST)
    mask = (kpos[:, None, :] <= pos[:, :, None]) & (kpos[:, None, :] >= 0)
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    lse_ref = jax.nn.logsumexp(s, axis=-1).reshape(B * Hq, T)
    np.testing.assert_allclose(np.asarray(lse.reshape(B * Hq, T)),
                               np.asarray(lse_ref), rtol=1e-5, atol=1e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, pos, kpos) * w)

    g_ref = jax.grad(loss(ref_attention), argnums=(0, 1, 2))(q, k, v)
    g_ker = jax.grad(loss(functools.partial(fused_attention, interpret=True)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


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


@pytest.mark.parametrize("B,T,D,N,h0_scale,lens", [
    (1, 200, 128, 16, 0.0, None),          # T not a multiple of the block
    (1, 256, 200, 16, 0.0, None),          # D padded to a channel block
    (2, 256, 256, 16, 1.0, None),          # a carried-in state
    (2, 384, 128, 16, 0.0, (150, 384)),    # a row with dt = 0 after 150
], ids=["t_tail", "d_tail", "h0", "dt0_rows"])
def test_selective_scan_kernels_match_chunked_path_and_reference(
        B, T, D, N, h0_scale, lens):
    """``fused_scan`` through the interpreted kernels, forward and custom
    VJP: y, the final state and the gradients in dt, B, C, x, A and h0
    against the chunked XLA path (``jax.grad``) and, where the state
    starts at zero, y and the gradients but h0 against the reference's
    step-by-step recurrence, row by row."""
    ks = jax.random.split(jax.random.fold_in(KEY, B * T + D), 8)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, D)) - 2.0)
    if lens is not None:
        dt = dt * (jnp.arange(T)[None, :] < jnp.array(lens)[:, None])[..., None]
    Bc = jax.random.normal(ks[1], (B, T, N))
    Cc = jax.random.normal(ks[2], (B, T, N))
    x = jax.random.normal(ks[3], (B, T, D))
    A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.3)
    h0 = h0_scale * jax.random.normal(ks[5], (B, D, N))
    dy = jax.random.normal(ks[6], (B, T, D))
    dh = jax.random.normal(ks[7], (B, D, N))
    args = (dt, Bc, Cc, x, A, h0)
    assert ss_ops.applies(T)

    def kernels(*a):
        return fused_scan(*a, 64, True)

    def chunked(*a):
        return ss_ref.selective_scan_ref(*a, 64)

    def loss(fn):
        def value(*a):
            y, h = fn(*a)
            return jnp.sum(y * dy) + jnp.sum(h * dh)
        return value

    def close(got, want):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * float(np.abs(want).max()))

    for got, want in zip(kernels(*args), chunked(*args)):
        close(got, want)
    g_ker = jax.grad(loss(kernels), argnums=range(6))(*args)
    for got, want in zip(g_ker, jax.grad(loss(chunked),
                                         argnums=range(6))(*args)):
        close(got, want)
    if h0_scale:
        return

    def ref(dt, Bc, Cc, x, A):
        return jnp.stack([JAMBA.recurrence(dt[b], Bc[b], Cc[b], x[b], A)
                          for b in range(B)])

    close(kernels(*args)[0], ref(*args[:5]))
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) * dy),
                     argnums=range(5))(*args[:5])
    g_ker = jax.grad(lambda *a: jnp.sum(kernels(*a, h0)[0] * dy),
                     argnums=range(5))(*args[:5])
    for got, want in zip(g_ker, g_ref):
        close(got, want)

"""jamba2-3b's mechanisms against the plain reference ``bench/reference/jamba.py``
at a tiny size on the CPU: the training step's loss and LoRA gradients,
prefill then decode through the paged engine, and the chunked selective
scan against the step-by-step recurrence."""
import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program  # noqa: E402
from bench.spec import BENCH, load_module  # noqa: E402
from repro.models import ssm  # noqa: E402

REF = load_module(BENCH / "reference" / "jamba.py")
KEY = jax.random.PRNGKey(5)
# the published layer pattern (attention at 7 of every 14), two periods;
# one KV head under 4 query heads
TINY = {
    "name": "tiny-jamba", "family": "jamba", "program_arch": "jamba2-3b",
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 4, "mamba_dt_rank": 8, "mamba_expand": 2,
    "mamba_proj_bias": False, "num_attention_heads": 4, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "vocab_size": 257,
    "lora": {"rank": 4, "alpha": 8, "adapters": 1,
             "targets": ["wq", "wv", "mamba_in", "mamba_out"]}}
CHUNK = 16


def _model(c=TINY, key=KEY):
    cfg = program.model_config(c, REF)
    cfg = dataclasses.replace(
        cfg, mamba=dataclasses.replace(cfg.mamba, chunk=CHUNK))
    w = REF.make_weights(c, key)
    ad = REF.make_adapter(c, jax.random.fold_in(key, 1))
    program.check_layout(cfg, w, ad)
    return cfg, w, ad


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def batch_and_reference(model):
    """A batch of two rows of 64 tokens (four scan chunks) and the
    reference's loss and LoRA gradients on it."""
    _, w, ad = model
    toks = jax.random.randint(jax.random.fold_in(KEY, 2), (2, 65), 0, 257)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return batch, REF.Reference(TINY).loss_and_grads(
        ad, w, batch["tokens"], batch["labels"], 2)


def test_layer_order_and_widths_follow_the_published_config(model):
    cfg = model[0]
    assert cfg.layer_kinds() == tuple(
        "attn" if i % 14 == 7 else "mamba" for i in range(28))
    assert cfg.attn.rope_theta is None
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.hd) == (4, 1, 16)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_train_step_matches_the_reference(model, batch_and_reference,
                                         remat):
    """Loss and every LoRA gradient leaf of the program's training step
    against the reference."""
    from repro.models.transformer import ExecConfig
    from repro.optim import adamw
    from repro.train.steps import TrainHParams, make_train_step
    cfg, w, ad = model
    batch, (loss, want) = batch_and_reference
    hp = TrainHParams(adamw=adamw.AdamWConfig(grad_clip=None))
    step = jax.jit(make_train_step(cfg, ExecConfig(remat=remat), hp))
    w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)
    _, opt, m = step(w32, ad, adamw.init(ad), batch, KEY)
    grads = jax.tree.map(lambda mu: mu / (1 - hp.adamw.b1), opt.mu)
    np.testing.assert_allclose(float(m["loss"]), float(loss), rtol=1e-5)
    got_leaves = jax.tree_util.tree_leaves_with_path(grads)
    want_leaves = jax.tree.leaves(want)
    assert len(got_leaves) == len(want_leaves) == 2 * 28
    for (path, g), r in zip(got_leaves, want_leaves):
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-4 * scale, rtol=2e-3,
                                   err_msg=jax.tree_util.keystr(path))


def test_prefill_then_decode_through_the_paged_engine(model):
    """Greedy tokens served through ``make_engine`` (chunked prefill, paged
    attention, Mamba state carried across decode steps) are the reference's
    best at every served position."""
    from repro.serve.api import Request, make_engine
    cfg, w, ad = model
    eng = make_engine(cfg, w, [ad], mode="paged", max_slots=2, max_len=64,
                      page_size=8, num_pages=32, prefill_chunk=16)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(0, 257, n).astype(np.int32),
                    max_new_tokens=10) for i, n in enumerate((21, 13, 30))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    R = REF.Reference(TINY)
    for r in reqs:
        assert len(r.generated) == 10
        P, n = len(r.prompt), len(r.generated)
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        targets = np.full(len(seq), -1, np.int32)
        targets[P - 1:] = r.generated
        gaps = np.asarray(R.gaps(w, ad, jnp.asarray(seq),
                                 jnp.asarray(targets)))[P - 1:]
        assert gaps.shape == (n,) and float(gaps.max()) < 1e-4, gaps


@pytest.mark.parametrize("T", [1, 7, 16, 37, 64])
def test_chunked_scan_is_the_step_by_step_recurrence(T):
    """``ssm._selective_scan`` in chunks of 16, with its padded last chunk,
    against ``jamba.recurrence``."""
    D, N = 12, 4
    ks = jax.random.split(jax.random.fold_in(KEY, T), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (1, T, D)) - 2.0)
    Bc = jax.random.normal(ks[1], (1, T, N))
    Cc = jax.random.normal(ks[2], (1, T, N))
    x = jax.random.normal(ks[3], (1, T, D))
    A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.3)
    y, _ = ssm._selective_scan(dt, Bc, Cc, x, A, jnp.zeros((1, D, N)), CHUNK)
    want = REF.recurrence(dt[0], Bc[0], Cc[0], x[0], A)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_mamba_block_products_run_at_highest_precision(model):
    """Every product of the Mamba block, in its gradient too, is staged at
    ``HIGHEST``; the attention block keeps the default."""
    from repro.models import attention
    cfg, w, ad = model
    take = functools.partial(jax.tree.map, lambda a: a[0])
    x = jax.random.normal(KEY, (1, 16, cfg.d_model))

    def mamba(x, la):
        return jnp.sum(ssm.apply_mamba_block(
            cfg, take(w["layers"][0]["mamba"]), x, lora=take(la))[0])

    def attn(x):
        return jnp.sum(attention.apply_attention_block(
            cfg, take(w["layers"][7]["attn"]), x, jnp.arange(16)[None],
            kind="attn")[0])

    text = str(jax.make_jaxpr(jax.grad(mamba, (0, 1)))(
        x, ad["layers"][0]))
    n = text.count("dot_general[")
    assert n >= 10 and text.count(
        "precision=(Precision.HIGHEST, Precision.HIGHEST)") == n
    text = str(jax.make_jaxpr(jax.grad(attn))(x))
    assert "dot_general[" in text and "Precision.HIGHEST" not in text

"""Optimizer, grad accumulation, data determinism, checkpoint, trainer
fault-tolerance, the launchers' compile cache."""
import pathlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.data.pipeline import ShardInfo, SyntheticLM
from repro.models import transformer as tfm
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.train import checkpoint as ckpt
from repro.train.steps import TrainHParams, make_train_step
from repro.train.trainer import Trainer, TrainerConfig

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def _np_adamw(p, g, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** step)
    vh = v / (1 - b2 ** step)
    return p - lr * mh / (np.sqrt(vh) + eps), m, v


def test_adamw_matches_numpy_reference():
    cfg = AdamWConfig(lr=0.01, grad_clip=None)
    p = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(32,)),
                          jnp.float32)}
    st = adamw.init(p)
    pn = np.asarray(p["w"]).copy()
    mn = np.zeros_like(pn)
    vn = np.zeros_like(pn)
    for step in range(1, 6):
        g = {"w": jnp.asarray(np.random.default_rng(step).normal(size=(32,)),
                              jnp.float32)}
        p, st, _ = adamw.apply_updates(cfg, p, g, st)
        pn, mn, vn = _np_adamw(pn, np.asarray(g["w"]), mn, vn, step, 0.01)
        np.testing.assert_allclose(np.asarray(p["w"]), pn, rtol=1e-5,
                                   atol=1e-6)


def test_grad_clip_bounds_update():
    cfg = AdamWConfig(lr=1.0, grad_clip=0.5)
    p = {"w": jnp.zeros((4,))}
    st = adamw.init(p)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, m = adamw.apply_updates(cfg, p, g, st)
    assert float(m["grad_norm"]) == pytest.approx(200.0, rel=1e-5)


def test_warmup_cosine_schedule():
    s = adamw.warmup_cosine(10, 100)
    assert float(s(jnp.asarray(0))) == 0.0
    assert float(s(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(s(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)


# ---------------------------------------------------------------------------
# grad accumulation
# ---------------------------------------------------------------------------

def test_microbatch_accumulation_matches_full_batch():
    cfg = reduce_config(get_config("llama3.2-1b"))
    from repro.core import lora as lora_lib
    params = tfm.init_params(cfg, KEY)
    lora = lora_lib.init_lora_params(cfg, KEY)
    lora = jax.tree.map(lambda x: x + 0.03, lora)
    ec = tfm.ExecConfig()
    toks = jax.random.randint(KEY, (8, 33), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    outs = {}
    for mb in (1, 4):
        step = make_train_step(cfg, ec, TrainHParams(
            microbatches=mb, adamw=AdamWConfig(lr=1e-2, grad_clip=None)))
        l2, _, m = step(params, lora, adamw.init(lora), batch, KEY)
        outs[mb] = (l2, float(m["loss"]))
    assert outs[1][1] == pytest.approx(outs[4][1], rel=1e-4)
    for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_resumable():
    ds = SyntheticLM(vocab_size=101, seed=4)
    b1 = ds.batch(7, 8, 32)
    b2 = ds.batch(7, 8, 32)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_data_sharding_partitions_global_batch():
    ds = SyntheticLM(vocab_size=53, seed=1)
    full = ds.batch(3, 8, 16)
    s0 = ds.batch(3, 8, 16, ShardInfo(0, 2))
    s1 = ds.batch(3, 8, 16, ShardInfo(1, 2))
    np.testing.assert_array_equal(np.concatenate([s0["tokens"], s1["tokens"]]),
                                  full["tokens"])


def test_data_is_learnable_structure():
    """Bigram process: successor entropy is far below uniform."""
    ds = SyntheticLM(vocab_size=257, seed=0)
    assert ds.entropy_bound() < np.log(257) * 0.5


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_gc():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "n": {"b": jnp.asarray(3)}}
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4, 5):
            ckpt.save(d, s, tree, keep=2)
        assert ckpt.latest_step(d) == 5
        back = ckpt.restore(d, jax.tree.map(jnp.zeros_like, tree))
        np.testing.assert_array_equal(np.asarray(back["a"]),
                                      np.asarray(tree["a"]))
        # gc kept only 2
        assert len(list(pathlib.Path(d).glob("step_*"))) == 2


def test_checkpoint_restore_to_abstract_target():
    tree = {"w": jnp.ones((4, 4), jnp.float32)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, tree)
        target = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
        back = ckpt.restore(d, target)
        np.testing.assert_array_equal(np.asarray(back["w"]), np.ones((4, 4)))


# ---------------------------------------------------------------------------
# trainer fault tolerance
# ---------------------------------------------------------------------------

def test_trainer_restart_after_injected_failure():
    cfg = reduce_config(get_config("llama3.2-1b"))
    ds = SyntheticLM(cfg.vocab_size, seed=3)
    with tempfile.TemporaryDirectory() as d:
        tc = TrainerConfig(seq_len=32, global_batch=4, steps=20, ckpt_dir=d,
                           ckpt_every=5, log_every=100)
        boom = {"armed": True}

        def hook(step):
            if step == 12 and boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected failure")

        tr = Trainer(cfg, tc, ds, step_hook=hook)
        log = tr.run_with_restarts()
        assert tr.fault.restarts == 1
        assert tr.step == 20
        # steps 11..20 were re-run from the checkpoint at 10
        assert len(log) >= 20


def test_trainer_without_checkpoints_raises_first_failure():
    """No checkpoint directory, nothing to restore: a failed step fails the
    run instead of restarting it from scratch."""
    cfg = reduce_config(get_config("llama3.2-1b"))
    ds = SyntheticLM(cfg.vocab_size, seed=3)
    tc = TrainerConfig(seq_len=32, global_batch=4, steps=3, log_every=100)

    def hook(step):
        if step == 1:
            raise RuntimeError("injected failure")

    tr = Trainer(cfg, tc, ds, step_hook=hook)
    with pytest.raises(RuntimeError, match="injected failure"):
        tr.run_with_restarts()
    assert tr.fault.restarts == 0 and tr.step == 1


def test_straggler_monitor_and_spare_swap():
    from repro.dist.fault import FaultCoordinator, RestartPolicy
    fc = FaultCoordinator(RestartPolicy(straggler_patience=2))
    for s in range(10):
        fc.on_step(s, 0.1)
    assert fc.on_step(10, 0.5) == "observe"       # 5x slower than EMA
    assert fc.on_step(11, 0.5) == "swap_spare"    # patience hit
    assert fc.decisions and fc.decisions[-1]["action"] == "swap_spare"


def test_elastic_resume_changes_nothing_numerically():
    """Restore on a 'different topology' (here: same host, fresh trainer) —
    training continues bit-identically thanks to stateless data indexing."""
    cfg = reduce_config(get_config("llama3.2-1b"))
    ds = SyntheticLM(cfg.vocab_size, seed=9)
    with tempfile.TemporaryDirectory() as d:
        tc = TrainerConfig(seq_len=32, global_batch=4, steps=10, ckpt_dir=d,
                           ckpt_every=5, log_every=100)
        t1 = Trainer(cfg, tc, ds)
        log1 = t1.run()
        # second trainer: restore at 5 and replay 6..10
        tc2 = TrainerConfig(seq_len=32, global_batch=4, steps=10, ckpt_dir=d,
                            ckpt_every=100, log_every=100)
        t2 = Trainer(cfg, tc2, ds)
        state = ckpt.restore(d, t2.train_state(), step=5)
        t2._load_state(state)
        log2 = t2.run()
        l1 = [r["loss"] for r in log1 if r["step"] > 5]
        l2 = [r["loss"] for r in log2]
        np.testing.assert_allclose(l1, l2, rtol=1e-6)


# ---------------------------------------------------------------------------
# launchers: where the persistent compilation cache lives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache is the checkout's fixed ``.jax_cache``."""
    from repro.launch.compile_cache import use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(pathlib.Path(__file__).resolve().parents[1]
                       / ".jax_cache")
            assert use_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

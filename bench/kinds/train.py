"""LoRA fine-tuning through the program's training step.

Set-up builds one object, the jitted ``make_train_step`` with its adapter
and AdamW state, and drives it through its first ``check_steps`` steps on
the input pipeline's first batches (every row differs); those steps compile
it. The window goes on with the same object and the same feed: the host
makes the next batch while the device runs the current step, and a step
counts when its loss is ready.

``correct``: the reference follows the first steps from the same adapter on
the same batches and is compared on each step's loss, on the first
gradient as the optimizer got it (its first moment after one step over
``1 - b1``), and on the adapter's change after the checked steps, each
gradient and change taken by its worst leaf. Under a control, the
reference one precision step down (or over half of each batch) stands in
for the program.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import program
from bench.harness import Check, Outcome, peak_bytes, seed_key
from bench.reference.common import adamw_step
from bench.tracing import Profile


class Feed:
    """Batches from the program's input pipeline, in order."""

    def __init__(self, vocab: int, seed: int, rows: int, seq_len: int):
        from repro.data.pipeline import make_dataset
        self.ds = make_dataset(vocab, seed)
        self.rows, self.seq_len, self.step = rows, seq_len, 0

    def host(self, step: int) -> dict:
        return self.ds.batch(step, self.rows, self.seq_len)

    def next(self) -> dict:
        import jax
        b = jax.device_put(self.host(self.step))
        self.step += 1
        return b


def leaf_gap(prog, ref) -> float:
    """Worst leaf of | |prog leaf| - |ref leaf| | over the larger of the ref
    leaf's norm and the median leaf's. Leaves whose reference norm is under
    a thousandth of the median leaf's move by round-off alone and are left
    out."""
    import jax
    p = np.array([np.linalg.norm(np.asarray(x, np.float64))
                  for x in jax.tree.leaves(prog)])
    r = np.array([np.linalg.norm(np.asarray(x, np.float64))
                  for x in jax.tree.leaves(ref)])
    med = float(np.median(r))
    keep = r >= 1e-3 * med
    return float(np.max(np.abs(p - r)[keep] / np.maximum(r[keep], med)))


def follow(R, w, lora0, feed, n: int, opt_cfg: dict, ctrl=None,
           half=False):
    """The reference's first n steps from ``lora0`` on the feed's first
    batches: (losses, first clipped gradient, adapter after n steps).
    ``half`` plants a fault: the second half of each batch is replaced by
    the first, so the mean is taken over half the rows."""
    import jax
    import jax.numpy as jnp
    rl = jax.tree.map(jnp.asarray, lora0)
    rm = jax.tree.map(jnp.zeros_like, rl)
    rv = jax.tree.map(jnp.zeros_like, rl)
    losses, g1 = [], None
    for i in range(n):
        b = feed.host(i)
        if half:
            k = len(b["tokens"]) // 2
            b = {x: np.concatenate([v[:k], v[:k]]) for x, v in b.items()}
        loss, grads = R.loss_and_grads(rl, w, jnp.asarray(b["tokens"]),
                                       jnp.asarray(b["labels"]), 1, ctrl)
        rl, rm, rv, clipped = adamw_step(rl, grads, rm, rv, i + 1, opt_cfg)
        losses.append(float(loss))
        if i == 0:
            g1 = clipped
    return losses, g1, jax.device_get(rl)


def numbers(got, want, lora0) -> dict:
    """The compared numbers of one run against the reference: worst step's
    relative loss gap, worst leaf of the first gradient and of the change
    after the checked steps."""
    import jax
    (lp, gp, ap), (lr, gr, ar) = got, want

    def change(a):
        return jax.tree.map(lambda x, y: np.asarray(x) - np.asarray(y),
                            a, lora0)
    return {"loss_gap": max(abs(p - r) / abs(r) for p, r in zip(lp, lr)),
            "grad_gap": leaf_gap(gp, gr),
            "update_gap": leaf_gap(change(ap), change(ar))}


def run(run) -> Outcome:
    import jax
    from repro.models.transformer import ExecConfig
    from repro.optim import adamw
    from repro.train.steps import TrainHParams, make_train_step
    cell = run.cell
    c, t = cell.config, cell.traffic
    ref = cell.reference()
    cfg = program.model_config(c, ref)
    key = seed_key(run.seed)
    w, lora = jax.jit(lambda k: (ref.make_weights(c, k), ref.make_adapter(
        c, jax.random.fold_in(k, 1))))(key)
    program.check_layout(cfg, w, lora)
    rows = t["tokens_per_step"] // t["seq_len"]
    opt_cfg = dict(t["adamw"])
    hp = TrainHParams(microbatches=rows // t["microbatch_rows"],
                      adamw=adamw.AdamWConfig(**opt_cfg))
    step = jax.jit(make_train_step(cfg, ExecConfig(remat=t["remat"]), hp),
                   donate_argnums=(1, 2))
    if run.wrap_step is not None:
        step = run.wrap_step(step)
    opt = adamw.init(lora)
    feed = Feed(cfg.vocab_size, run.seed, rows, t["seq_len"])

    # set-up: the object the window drives, through its first steps
    lora0 = jax.device_get(lora)
    n_check = t["check_steps"]
    losses, mu1 = [], None
    for i in range(n_check):
        lora, opt, m = step(w, lora, opt, feed.next(),
                            jax.random.fold_in(key, i))
        losses.append(m["loss"])
        if i == 0:
            mu1 = jax.device_get(opt.mu)
    lora_n = jax.device_get(lora)
    losses = [float(x) for x in losses]

    prof = Profile(run.out_dir / "trace" / cell.name)
    tr = t["trace"]
    compiles0 = run.compiles[0]
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    end = t0 + run.seconds
    done, prev, traced_steps, k = 0, None, 0, n_check
    while True:
        if run.trace and not (prof.active or prof.done) and \
                time.perf_counter() - t0 >= run.seconds * tr["start_fraction"]:
            prof.start()
            traced_steps = 0
        with prof.span("next_batch"):
            batch = feed.next()
        with prof.span("train_step"):
            lora, opt, m = step(w, lora, opt, batch,
                                jax.random.fold_in(key, k))
        k += 1
        if prev is not None:
            with prof.span("wait_step"):
                prev.block_until_ready()
            done += 1
            if prof.active:
                traced_steps += 1
                if traced_steps >= tr["steps"]:
                    prof.stop()
        prev = m["loss"]
        if time.perf_counter() >= end:
            break
    prev.block_until_ready()
    done += 1
    t_close = time.perf_counter()
    if prof.active:
        prof.stop()
    window_compiles = run.compiles[0] - compiles0
    run.note(f"window: {t_close - t0:.3f}s, {done} steps of "
             f"{t['tokens_per_step']} tokens; compiles in window "
             f"{window_compiles}; setup {setup_s:.3f}s; losses of the first "
             f"steps {losses}")

    mem = peak_bytes()
    del lora, opt, m, prev, batch, step
    gc.collect()
    trace = prof.read()

    # the reference, through the same first steps
    R = ref.Reference(c)
    want = follow(R, w, lora0, feed, n_check, opt_cfg)
    if run.control == "half_batch":
        got = follow(R, w, lora0, feed, n_check, opt_cfg, half=True)
    elif run.control:
        got = follow(R, w, lora0, feed, n_check, opt_cfg, ctrl=run.control)
    else:
        got = (losses, jax.tree.map(
            lambda m: np.asarray(m) / (1 - opt_cfg["b1"]), mu1), lora_n)
    lim = c["limits"]
    checks = [Check(name, value, lim[name]) for name, value in
              numbers(got, want, lora0).items()]
    run.note(f"check{' of control ' + run.control if run.control else ''}: "
             f"reference losses {want[0]}, compared {got[0]}")
    tokens = done * t["tokens_per_step"]
    flops = ref.train_step_flops(c, rows, t["seq_len"])
    return Outcome(
        end_to_end={"setup_s": setup_s,
                    "train_tok_per_s": tokens / (t_close - t0)},
        data={"kind": "train", "trace": trace, "peaks": run.peaks,
              "model_flops": flops * traced_steps},
        checks=checks, attempted=done, failed=0, memory_peak_bytes=mem)

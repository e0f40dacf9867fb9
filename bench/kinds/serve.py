"""Open-loop multi-adapter serving through the paged engine.

Set-up makes the weights and adapters from the seed in one program, builds
the engine through ``make_engine``, and runs every step signature the mix
can reach once. The window then submits each request when it falls due,
calls ``engine.step()``, and stamps the tokens each tick adds to every
request in flight; it sleeps only when nothing is queued or running.
Time to first token counts from the request's due time; a request due in
the window that has no first token when it closes counts at its age then.

``correct``: a sample of the requests finished in the window, drawn from the
seed and always holding the longest, goes through the plain reference once
over prompt and served tokens; the widest gap by which a served token's
reference logit lies below the reference's best is held to the
configuration's ``logit_gap`` limit. Under a control the token the control
puts first at each of those positions stands in for the served one.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import arrivals, program
from bench.harness import Check, Outcome, peak_bytes, seed_key
from bench.tracing import Profile


def make_model(ref, c: dict, key):
    """Weights and every adapter, in one program on the device."""
    import jax

    def make(key):
        adapters = [ref.make_adapter(c, jax.random.fold_in(key, 1 + i))
                    for i in range(c["lora"]["adapters"])]
        return ref.make_weights(c, key), adapters
    return jax.jit(make)(key)


def warm_up(eng, t: dict, vocab: int) -> int:
    """Run every (chunk, table width) signature the mix can reach, then one
    request through the public API (the tick's own small programs), and
    zero every count of recycled slots the arena can be asked to reset.
    Returns the number of step signatures."""
    import jax
    import jax.numpy as jnp
    from repro.serve.api import Request
    from repro.serve.scheduler import bucketize
    e = t["engine"]
    B = eng.layout.max_slots
    shortest = t["system_prompt_tokens"] + t["user_tokens"]["min"]
    nb_min = bucketize(eng.layout.blocks_for(min(e["prefill_chunk"],
                                                 shortest)),
                       eng.block_buckets)
    sigs = [(C, nb) for C in eng.chunk_buckets for nb in eng.block_buckets
            if nb >= nb_min]
    zeros = jnp.zeros((B,), jnp.int32)
    idx = zeros if eng.adapters is not None else None
    rng = jax.random.split(jax.random.PRNGKey(0))[1]
    for C, nb in sigs:
        out, eng.cache, _ = eng._step(
            eng.params, eng.adapters, eng.cache, jnp.zeros((B, C), jnp.int32),
            zeros, zeros, jnp.full((B, nb), -1, jnp.int32), idx, rng,
            jnp.zeros((B,), jnp.float32))
        out.block_until_ready()
    for n in range(1, B + 1):
        eng.cache = eng.arena.reset(eng.cache, list(range(n)))
    prompt = np.arange(shortest, dtype=np.int32) % vocab
    eng.submit(Request(uid=-1, prompt=prompt, max_new_tokens=2))
    eng.drain()
    eng.release_prefix_cache()
    return len(sigs)


def _counters(eng) -> dict:
    st = eng.stats()
    pc = st.prefix_cache
    return {"ticks": st.ticks, "prefill_tokens": st.prefill_tokens,
            "decode_tokens": st.decode_tokens,
            "prefix_hit_tokens": pc.hit_tokens if pc else 0,
            "prefix_enabled": bool(pc and pc.enabled),
            "preemptions": st.scheduler.preemptions if st.scheduler else 0,
            "signatures": st.compile.compiled_steps}


def _sample(done, check: dict, seed: int):
    """The longest finished request, then others in an order drawn from
    the seed, until both minimums are met."""
    if not done:
        return []
    order = sorted(done, key=lambda r: -(len(r.prompt) + len(r.generated)))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    pick = [order[0]] + [rest[i] for i in rng.permutation(len(rest))]
    out, toks = [], 0
    for r in pick:
        if len(out) >= check["min_requests"] and toks >= check["min_tokens"]:
            break
        out.append(r)
        toks += len(r.generated)
    return out


def logit_gaps(R, w, adapters, reqs, max_len: int, ctrl=None):
    """Per served token of each request, the reference's gap (under a
    control, the gap of the control's first token there)."""
    import jax.numpy as jnp
    gaps = []
    for r in reqs:
        P, n = len(r.prompt), len(r.generated)
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.generated[:-1], np.int32)])
        tokens = np.zeros(max_len, np.int32)
        tokens[:len(seq)] = seq
        targets = np.full(max_len, -1, np.int32)
        targets[P - 1:P - 1 + n] = r.generated
        g = R.gaps(w, adapters[r.adapter_id], jnp.asarray(tokens),
                   jnp.asarray(targets), ctrl)
        gaps.append(np.asarray(g)[P - 1:P - 1 + n])
    return gaps


def run(run) -> Outcome:
    from repro.serve.api import Request, make_engine
    cell = run.cell
    c, t = cell.config, cell.traffic
    e = t["engine"]
    ref = cell.reference()
    cfg = program.model_config(c, ref)
    w, adapters = make_model(ref, c, seed_key(run.seed))
    program.check_layout(cfg, w, adapters[0])
    eng = make_engine(cfg, w, adapters, mode="paged",
                      max_slots=e["max_slots"], max_len=e["max_len"],
                      page_size=e["page_size"],
                      num_pages=c["serve"]["num_pages"],
                      prefill_chunk=e["prefill_chunk"],
                      seed=run.seed & 0x7FFFFFFF)
    n_sigs = warm_up(eng, t, cfg.vocab_size)
    mix = arrivals.serve_mix(t, len(adapters), cfg.vocab_size, run.seed,
                             run.seconds)
    if run.wrap_engine is not None:
        run.wrap_engine(eng)

    prof = Profile(run.out_dir / "trace" / cell.name)
    calls = []
    if run.trace:
        step_fn = eng._step

        def probe(*args):                 # lens, clens of each traced step
            if prof.active:
                calls.append((np.asarray(args[4]), np.asarray(args[5])))
            return step_fn(*args)
        eng._step = probe
    tr = t["trace"]
    trace_at = run.seconds * tr["start_fraction"]
    trace_until = min(trace_at + tr["seconds"], run.seconds)

    before = _counters(eng)
    compiles0 = run.compiles[0]
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    end = t0 + run.seconds
    due = [t0 + a.due_s for a in mix]
    reqs, live, seen, first, last = [], {}, {}, {}, {}
    itl, late = [], []
    produced, i = 0, 0
    while True:
        now = time.perf_counter()
        if run.trace and not (prof.active or prof.done) \
                and now - t0 >= trace_at:
            prof.start()
        if prof.active and now - t0 >= trace_until:
            prof.stop()
        if now >= end:
            break
        with prof.span("submit"):
            while i < len(mix) and due[i] <= now:
                a = mix[i]
                r = Request(uid=i, prompt=a.prompt,
                            max_new_tokens=a.max_new_tokens,
                            adapter_id=a.adapter_id)
                eng.submit(r)
                late.append(now - due[i])
                reqs.append(r)
                live[i], seen[i] = r, 0
                i += 1
        if not live:
            nxt = due[i] if i < len(mix) else end
            with prof.span("wait_arrival"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        with prof.span("engine_step"):
            eng.step()
        now = time.perf_counter()
        for uid, r in list(live.items()):
            k = len(r.generated) - seen[uid]
            if k > 0:
                if seen[uid] == 0:
                    first[uid] = now
                else:
                    itl.append(now - last[uid])
                itl.extend([0.0] * (k - 1))
                produced += k
                last[uid], seen[uid] = now, len(r.generated)
            if r.done:
                del live[uid]
    if prof.active:
        prof.stop()
    t_close = time.perf_counter()
    window_compiles = run.compiles[0] - compiles0
    after = _counters(eng)
    counters = {k: after[k] - before[k] for k in before
                if k != "prefix_enabled"}
    counters["prefix_enabled"] = after["prefix_enabled"]

    ttft = [(first[r.uid] if r.uid in first else t_close) - due[r.uid]
            for r in reqs]
    n_first = sum(r.uid in first for r in reqs)
    # a growing backlog shows as later requests waiting longer
    thirds = [1e3 * float(np.median(x)) for x in np.array_split(ttft, 3)
              if len(x)]
    run.note(f"generator: {len(reqs)} requests due in {run.seconds:g}s, "
             f"submitted late by mean {1e3 * np.mean(late):.3f} ms, max "
             f"{1e3 * np.max(late):.3f} ms")
    run.note(f"window: {t_close - t0:.3f}s, {counters['ticks']} ticks, "
             f"{produced} tokens out, {n_first}/{len(reqs)} first tokens, "
             f"{len(itl)} gaps; prefill {counters['prefill_tokens']}, "
             f"prefix hits {counters['prefix_hit_tokens']} tokens, "
             f"preemptions {counters['preemptions']}; compiles in window "
             f"{window_compiles}; step signatures warmed {n_sigs}, "
             f"used {after['signatures']}; setup {setup_s:.3f}s; time to "
             f"first token p90 {1e3 * np.percentile(ttft, 90):.1f} ms, median "
             f"by thirds of the window {thirds} ms")

    mem = peak_bytes()
    done = [r for r in reqs if r.done]
    vocab = cfg.vocab_size
    failed = sum(1 for r in done if r.finish_reason != "length"
                 or len(r.generated) != r.max_new_tokens
                 or not all(0 <= x < vocab for x in r.generated))
    sample = _sample([r for r in done if r.finish_reason == "length"],
                     t["check"], run.seed)
    flops = sum(ref.serve_step_flops(c, lens, clens) for lens, clens in calls)
    del eng, calls
    if run.trace:
        del probe, step_fn
    gc.collect()

    trace = prof.read()
    R = ref.Reference(c)
    gaps = logit_gaps(R, w, adapters, sample, e["max_len"],
                      run.control or None)
    worst = float(max((g.max() for g in gaps), default=float("inf")))
    run.note(f"check{' of control ' + run.control if run.control else ''}: "
             f"{len(sample)} requests, {sum(len(g) for g in gaps)} served "
             f"tokens, widest logit gap {worst!r} "
             f"(limit {c['limits']['logit_gap']})")
    return Outcome(
        end_to_end={
            "setup_s": setup_s,
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
            "itl_p95_ms": 1e3 * float(np.percentile(itl, 95)) if itl
            else float("nan"),
            "out_tok_per_s": produced / (t_close - t0)},
        data={"kind": "serve", "trace": trace, "counters": counters,
              "ttft_thirds_ms": thirds,
              "model_flops": flops, "peaks": run.peaks},
        checks=[Check("logit_gap", worst, c["limits"]["logit_gap"])],
        attempted=len(reqs), failed=failed, memory_peak_bytes=mem,
        valid=failed == 0)

"""Device time by the program's own layers.

A device op event names its HLO instruction, not the ``named_scope`` path it
was built under; the compiled program's text carries that path in each
instruction's ``op_name`` metadata. ``buckets`` maps each instruction to a
layer scope of ``repro.obs``: the innermost one on its op_name path (through
``transpose(...)``, ``jvp(...)`` and ``vmap(...)``) or, for a fusion, that
of the matmuls fused into it, whatever layer its root names; a fusion of
other work from two layers counts under a joint bucket such as
``attn+mlp``. ``step_self_times`` counts each op's self time, every instant
credited to the innermost op running then (a ``while`` keeps only its own
overhead), over complete executions of one program inside the traced
window, so the buckets and ``unscoped`` add up to the device's busy time
there.

Readers take the program's text from ``data["hlo_text"]``. Run as a script,
this runs ``bench/run.py`` traced, fetches the step's text after the window,
and adds the scope metrics, ``breakdown.device_scopes`` and how the buckets
add up to its line:

    python bench/scopes.py --workload nemo-lora-train --seed 7 --seconds 51
"""
from __future__ import annotations

import functools
import pathlib
import re
import sys
import time
from bisect import bisect_right
from collections import Counter
from typing import Dict, List, Optional, Tuple

UNSCOPED = "unscoped"
TRAIN_MODULE = "jit_step"     # the jitted ``make_train_step`` of kinds/train
# per-layer metrics read from the scopes (files under metrics/)
METRICS = ("attn_ms.train", "mlp_ms.train", "head_loss_ms.train")

_WRAP = re.compile(r"^(?:transpose|jvp|vmap)\((.*)\)$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
MATMULS = ("dot", "convolution")


def program_obs():
    """The program's ``repro.obs``, or None where the program names no
    layers (a program older than the scopes reads nothing, and no fault)."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def layer_scopes(op_name: str) -> List[str]:
    """The layer scopes on an op_name path, outermost first."""
    obs = program_obs()
    layers = obs.LAYERS if obs else ()
    out = []
    for part in op_name.split("/"):
        while (m := _WRAP.match(part)):
            part = m.group(1)
        if part in layers:
            out.append(part)
    return out


def scope_of(op_name: str) -> str:
    """The innermost layer scope on the path, or ``unscoped``."""
    found = layer_scopes(op_name)
    return found[-1] if found else UNSCOPED


@functools.lru_cache(maxsize=4)
def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name, over every computation of the text."""
    from repro.roofline.hlo_parse import HloModule
    return {op.name: op.scope for ops in HloModule(hlo_text).comps.values()
            for op in ops}


@functools.lru_cache(maxsize=4)
def buckets(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its bucket, the layer whose work it is. A fusion
    holds the work of the instructions fused into it. Where some are
    matmuls, their layer is its bucket: the elementwise work XLA fuses
    around a matmul (a residual add, a norm's reduction, the loss's max
    over the vocabulary) is small beside it, and a matmul then counts under
    its own layer however it was fused. Else the bucket is every layer it
    holds, joined by ``+`` in ``obs.LAYERS`` order; ``unscoped`` where none."""
    from repro.roofline.hlo_parse import HloModule
    obs = program_obs()
    order = obs.LAYERS if obs else ()
    comps = HloModule(hlo_text).comps
    held: Dict[str, Tuple[frozenset, frozenset]] = {}

    def holds(op) -> Tuple[frozenset, frozenset]:
        """(layers of the matmuls, layers of all the work) of ``op``."""
        own = frozenset(layer_scopes(op.scope)[-1:])
        mm, every = (own if op.opcode in MATMULS else frozenset()), own
        if op.opcode == "fusion":
            for callee in _CALLS.findall(op.rest):
                if callee not in held:
                    held[callee] = (frozenset(), frozenset())  # HLO: no cycles
                    parts = [holds(o) for o in comps.get(callee, ())]
                    held[callee] = (frozenset().union(*(p[0] for p in parts)),
                                    frozenset().union(*(p[1] for p in parts)))
                mm, every = mm | held[callee][0], every | held[callee][1]
        return mm, every

    def bucket(op) -> str:
        mm, every = holds(op)
        return "+".join(sorted(mm or every, key=order.index)) or UNSCOPED

    return {op.name: bucket(op) for ops in comps.values() for op in ops}


def instruction(event_name: str) -> str:
    """'%fusion.16 = f32[4,64]{...} fusion(...)' (TPU) or 'fusion.16'
    (CPU) -> 'fusion.16'."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def self_times(ops) -> Dict[str, float]:
    """Seconds of each op name's self time: every instant in which some op
    runs is credited to the one that started last (of those that started
    together, the shorter), so the self times add up to the union of the
    intervals. An op that outruns its parent keeps the time past the
    parent's end."""
    out: Dict[str, float] = {}
    order = sorted(ops, key=lambda o: (o[0], -o[1]))
    cuts = sorted({t for s, e, _ in ops for t in (s, e)})
    running: List[Tuple[float, str]] = []    # (end, name), by start
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        running = [r for r in running if r[0] > a]
        while i < len(order) and order[i][0] <= a:
            if order[i][1] > a:
                running.append((order[i][1], order[i][2]))
            i += 1
        if running:
            name = running[-1][1]
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def step_ops(trace, module: str) -> Tuple[int, list]:
    """(complete executions of ``module``, the ops that ran in them as
    (start, end, instruction)) on the first device. An execution is
    complete when it lies in the traced window and other executions end
    before it and start after it: the profiler cuts the ones in flight when
    it starts or stops, and they may still fall inside the window."""
    dev = next(iter(trace.devices.values()), None)
    if dev is None or not dev.modules:
        return 0, []
    w0, w1 = trace.window
    first_end = min(e for _, e, _ in dev.modules)
    last_start = max(s for s, _, _ in dev.modules)
    runs = sorted((s, e) for s, e, n in dev.modules
                  if n == module and w0 <= s and e <= w1
                  and first_end <= s and e <= last_start)
    starts = [s for s, _ in runs]
    inside = []
    for s, e, name in dev.ops:
        i = bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            inside.append((s, min(e, runs[i][1]), instruction(name)))
    return len(runs), inside


def step_self_times(trace, module: str) -> Tuple[int, Dict[str, float]]:
    """(complete executions of ``module``, self seconds by instruction)."""
    n, ops = step_ops(trace, module)
    return n, self_times(ops)


def by_bucket(per_op: Dict[str, float], bucket_of: Dict[str, str]
              ) -> Dict[str, float]:
    """Self seconds summed by bucket."""
    out: Dict[str, float] = {}
    for op, sec in per_op.items():
        k = bucket_of.get(op, UNSCOPED)
        out[k] = out.get(k, 0.0) + sec
    return out


def train_ms(data, *layers: str) -> Optional[float]:
    """Device milliseconds of self time, per complete execution of the
    training step, of the ops whose work lies wholly within ``layers``
    (joint buckets of them included); None where no such op ran."""
    tr, text = data.get("trace"), data.get("hlo_text")
    if data["kind"] != "train" or tr is None or text is None:
        return None
    n, per_op = step_self_times(tr, TRAIN_MODULE)
    hit = [sec for k, sec in by_bucket(per_op, buckets(text)).items()
           if k != UNSCOPED and set(k.split("+")) <= set(layers)]
    return 1e3 * sum(hit) / n if n and hit else None


def device_scopes(trace, hlo_text: str, module: str, k: int = 10
                  ) -> List[list]:
    """The k buckets with most self seconds in complete executions of
    ``module``, then the ``unscoped`` row."""
    _, per_op = step_self_times(trace, module)
    sec = by_bucket(per_op, buckets(hlo_text))
    rest = sec.pop(UNSCOPED, 0.0)
    top = sorted(sec.items(), key=lambda x: -x[1])[:k]
    return [[n, v] for n, v in top] + [[UNSCOPED, rest]]


def traced(run, run_cell=None) -> dict:
    """Drive ``run`` (a training cell, traced) through the harness's
    ``run_cell`` with the step it drives captured; after the window, fetch
    that step's compiled text and add to the result line the scope
    metrics, ``breakdown.device_scopes`` and, under ``scopes``, how the
    buckets add up."""
    from bench import harness
    seen, kept = {}, {}

    def capture(step):
        def call(*args):
            if "lowered" not in seen:     # set-up: the first check step
                seen["lowered"] = step.lower(*args)
            return step(*args)
        return call

    run.wrap_step = capture
    line = (run_cell or harness.run_cell)(run, keep_data=kept.update)
    t0 = time.perf_counter()
    text = seen["lowered"].compile().as_text()
    fetch_s = time.perf_counter() - t0
    data = dict(kept, hlo_text=text)
    bucket_of = buckets(text)
    info = {"hlo_fetch_s": fetch_s,
            "instructions": Counter(bucket_of.values())}
    for name in METRICS:
        v = run.cell.reader(name).read(data)
        if v is not None:
            line["metrics"][name] = {"value": float(v), "unit": "ms"}
    tr = data.get("trace")
    if tr is not None and tr.devices:
        from bench.tracing import merge
        n, ops = step_ops(tr, TRAIN_MODULE)
        per_op = self_times(ops)
        busy = sum(e - s for s, e in merge((s, e) for s, e, _ in ops))
        unscoped = sorted(((op, s) for op, s in per_op.items()
                           if bucket_of.get(op, UNSCOPED) == UNSCOPED),
                          key=lambda x: -x[1])
        line.setdefault("breakdown", {})["device_scopes"] = device_scopes(
            tr, text, TRAIN_MODULE)
        info.update(
            steps=n, busy_in_steps_s=busy, self_sum_s=sum(per_op.values()),
            matched_share=sum(s for op, s in per_op.items()
                              if op in bucket_of) / busy if busy else None,
            top_unscoped=[[op, op_names(text).get(op, "?"), s]
                          for op, s in unscoped[:10]])
    line["scopes"] = info
    return line


def main(argv=None) -> int:
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench import harness, run as bench_run
    import jax
    # the cache's key leaves op_name metadata out by default, so an entry
    # another commit compiled would bring that commit's scopes; with it in,
    # the fetch after the window loads the very program the window ran
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    argv = list(sys.argv[1:] if argv is None else argv)
    plain = harness.run_cell
    harness.run_cell = lambda run, keep_data=None: traced(run, plain)
    try:
        return bench_run.main(argv + ["--trace", "1"])   # looks run_cell up
    finally:
        harness.run_cell = plain


if __name__ == "__main__":
    sys.exit(main())

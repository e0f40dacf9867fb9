"""One run of one cell: the kind's window, the per-layer readers, the
correctness numbers and the result line."""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from bench.spec import CHECKOUT, Cell

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Run:
    """What a kind's runner is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                    # perf_counter at process start
    peaks: dict
    out_dir: object = CHECKOUT / "bench_out"
    # what stands in the program's place in the comparison (calibration
    # only, never in a benchmark run): "" the program; a control of
    # ``reference.common.CONTROLS``; "half_batch" (training) the reference
    # over half of each batch, a planted fault
    control: str = ""
    # test seams: wrap the engine or the training step the window drives
    wrap_engine: Optional[Callable] = None
    wrap_step: Optional[Callable] = None
    compiles: List[int] = field(default_factory=lambda: [0])

    def note(self, msg: str) -> None:
        print(f"{self.cell.name}: {msg}", file=sys.stderr, flush=True)


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a kind's runner returns."""
    end_to_end: Dict[str, float]
    data: dict                        # what per-layer readers read
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    valid: bool = True                # False: something other than a limit
    #                                   failed (wrong finish, bad token)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits of it used."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _compile_listener(run: Run):
    """A listener that counts every program JAX builds or loads."""
    def listener(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            run.compiles[0] += 1
    return listener


def peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def run_cell(run: Run, keep_data: Optional[Callable] = None) -> dict:
    """Drive one run; return the result line as a dict. ``keep_data``, if
    given, is handed what the per-layer readers read."""
    import jax
    from jax._src import monitoring
    listener = _compile_listener(run)
    monitoring.register_event_duration_secs_listener(listener)
    try:
        out = run.cell.runner().run(run)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    if keep_data is not None:
        keep_data(out.data)
    correct = out.valid and all(c.ok for c in out.checks)
    if run.trace:
        metrics = {}
        for m in run.cell.per_layer:
            v = run.cell.reader(m["name"]).read(out.data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(out.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in run.cell.end_to_end}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": bool(correct), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    trace = out.data.get("trace")       # tracing.Trace of the traced segment
    if run.trace and trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        line["breakdown"] = {"device_ops": trace.top_ops(10),
                             "idle_gaps": trace.idle_gaps(10)}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def emit(line: dict) -> None:
    """The compared numbers last on stderr, the result last on stdout."""
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)

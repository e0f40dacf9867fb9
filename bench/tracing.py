"""Profiler traces: taking one around part of a window, and reducing it.

A traced segment is a JAX profiler session with the Python tracer off, so
the host plane holds only the harness's own spans (``span(name)``, written
as ``jax.profiler.TraceAnnotation``) and the runtime's events. Device
operations come from the ``XLA Ops`` line of each ``/device:*`` plane and
program executions from its ``XLA Modules`` line; host spans and device
events share one clock.
"""
from __future__ import annotations

import contextlib
import glob
import re
import shutil
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

SPANS = ("trace_window", "wait_arrival", "submit", "engine_step",
         "next_batch", "train_step", "wait_step")


class Profile:
    """Start and stop one profiler session; ``span`` is a no-op while no
    session is open, so untraced runs carry no annotations."""

    def __init__(self, directory):
        self.dir = str(directory)
        self.active = False
        self.done = False
        self._window = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True
        self._window = jax.profiler.TraceAnnotation("trace_window")
        self._window.__enter__()

    def stop(self) -> None:
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def read(self) -> Optional["Trace"]:
        """The reduced trace of the stopped session (None if none ran);
        the files are removed."""
        if not self.done:
            return None
        trace = read(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: Sequence[Tuple[float, float]], s: float, e: float) -> float:
    """Length of [s, e) covered by disjoint sorted intervals."""
    starts = [a for a, _ in merged]
    i = max(bisect_right(starts, s) - 1, 0)
    tot = 0.0
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        tot += max(0.0, min(b, e) - max(a, s))
        i += 1
    return tot


_HASH = re.compile(r"\(\d+\)$")


def op_key(name: str) -> str:
    """'%fusion.16 = f32[4,64]{1,0:T(4,128)} fusion(...)' ->
    'fusion.16 f32[4,64]'."""
    lhs, _, rhs = name.partition(" = ")
    shape = re.split(r"[{ ]", rhs.strip(), maxsplit=1)[0] if rhs else ""
    return f"{lhs.lstrip('%')} {shape}".strip()


@dataclass
class Device:
    ops: List[Tuple[float, float, str]] = field(default_factory=list)
    modules: List[Tuple[float, float, str]] = field(default_factory=list)


@dataclass
class Trace:
    """A reduced trace; times in seconds on the profiler's clock."""
    devices: Dict[str, Device]
    spans: List[Tuple[float, float, str]]
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, dev: Device) -> List[Tuple[float, float]]:
        w0, w1 = self.window
        return merge((max(s, w0), min(e, w1)) for s, e, _ in dev.ops
                     if e > w0 and s < w1)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([sum(e - s for s, e in self.busy(d))
                              for d in self.devices.values()]))

    def _first(self) -> Device:
        return next(iter(self.devices.values()), Device())

    def module_time(self, prefix: str) -> Tuple[int, float]:
        """(executions, device seconds) of programs whose name starts with
        ``prefix``, on the first device."""
        dev = self._first()
        hits = [e - s for s, e, n in dev.modules if n.startswith(prefix)]
        return len(hits), float(sum(hits))

    def self_time(self, span: str) -> Tuple[int, float]:
        """(count, host seconds) of spans named ``span`` with the device's
        busy time inside them taken out."""
        dev = self._first()
        busy = self.busy(dev)
        hits = [(s, e) for s, e, n in self.spans if n == span]
        return len(hits), float(sum((e - s) - overlap(busy, s, e)
                                    for s, e in hits))

    def top_ops(self, k: int = 10) -> List[list]:
        """The k device operations that took most time, by name."""
        dev = self._first()
        starts = [s for s, _, _ in dev.modules]
        tot: Dict[str, float] = {}
        for s, e, name in dev.ops:
            i = bisect_right(starts, s) - 1
            mod = dev.modules[i][2] if i >= 0 else "?"
            key = f"{mod}:{op_key(name)}"
            tot[key] = tot.get(key, 0.0) + (e - s)
        return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The k longest stretches with no operation on the first device,
        each named by the innermost harness span around its middle."""
        dev = self._first()
        busy = self.busy(dev)
        w0, w1 = self.window
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            mid = (s + e) / 2
            around = [(a, n) for a, b, n in self.spans
                      if a <= mid < b and n != "trace_window"]
            label = max(around)[1] if around else "outside_spans"
            out.append([label, e - s])
        return out


def read(directory) -> Trace:
    """Reduce the one ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    devices: Dict[str, Device] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = Device()
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                               for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = sorted(
                        (e.start_ns * 1e-9, e.end_ns * 1e-9,
                         _HASH.sub("", e.name)) for e in line.events)
            if dev.ops:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                          for e in line.events if e.name in SPANS]
    win = [(s, e) for s, e, n in spans if n == "trace_window"]
    if not win:
        raise RuntimeError("the trace holds no trace_window span")
    return Trace(devices=devices, spans=sorted(spans), window=win[0])

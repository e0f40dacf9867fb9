"""Reducing a trace to per-layer numbers, on a synthesized trace."""
import pytest

from bench import tracing
from bench.spec import BENCH, load_module
from bench.tracing import Device, Trace


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


@pytest.fixture
def trace():
    # window 0..10 s; device busy 1-3 and 2.5-4 (one op nested in time),
    # 6-7; two step executions; host spans around them
    dev = Device(
        ops=[(1.0, 3.0, "%fusion.1 = f32[4,8]{1,0} fusion(%a)"),
             (2.5, 4.0, "%convert.2 = f32[16]{0} convert(%b)"),
             (6.0, 7.0, "%fusion.1 = f32[4,8]{1,0} fusion(%a)")],
        modules=[(1.0, 4.0, "jit__step_fn"), (6.0, 7.0, "jit__step_fn")])
    spans = [(0.0, 10.0, "trace_window"), (0.5, 4.5, "engine_step"),
             (4.5, 5.9, "wait_arrival"), (5.9, 7.2, "engine_step"),
             (7.2, 10.0, "submit")]
    return Trace(devices={"/device:TPU:0": dev}, spans=spans,
                 window=(0.0, 10.0))


def test_merge_and_overlap():
    m = tracing.merge([(3, 4), (1, 2), (1.5, 2.5), (5, 6)])
    assert m == [(1, 2.5), (3, 4), (5, 6)]
    assert tracing.overlap(m, 2.0, 5.5) == pytest.approx(0.5 + 1 + 0.5)


def test_busy_union_and_idle_share(trace):
    assert trace.busy_s == pytest.approx(4.0)
    assert trace.window_s == pytest.approx(10.0)
    got = _reader("idle_share.serve").read({"kind": "serve", "trace": trace})
    assert got == pytest.approx(60.0)
    assert _reader("idle_share.train").read(
        {"kind": "serve", "trace": trace}) is None


def test_host_time_per_tick(trace):
    n, host = trace.self_time("engine_step")
    assert n == 2 and host == pytest.approx((4.0 - 3.0) + (1.3 - 1.0))
    got = _reader("host_ms_per_tick.serve").read(
        {"kind": "serve", "trace": trace})
    assert got == pytest.approx(650.0)


def test_step_time_and_mfu(trace):
    assert trace.module_time("jit__step_fn") == (2, pytest.approx(4.0))
    data = {"kind": "serve", "trace": trace, "model_flops": 2e12,
            "peaks": {"bf16_flops_per_s": 1e12}}
    assert _reader("step_ms.serve").read(data) == pytest.approx(2000.0)
    assert _reader("step_mfu.serve").read(data) == pytest.approx(50.0)
    train = {"kind": "train", "trace": trace, "model_flops": 5e12,
             "peaks": {"bf16_flops_per_s": 1e12}}
    assert _reader("step_mfu.train").read(train) == pytest.approx(50.0)


def test_op_names_and_breakdown(trace):
    assert tracing.op_key("%fusion.1 = f32[4,8]{1,0} fusion(%a)") == \
        "fusion.1 f32[4,8]"
    top = trace.top_ops(2)
    assert top[0] == ["jit__step_fn:fusion.1 f32[4,8]", pytest.approx(3.0)]
    gaps = trace.idle_gaps(3)
    assert gaps[0] == ["submit", pytest.approx(3.0)]
    assert gaps[1] == ["wait_arrival", pytest.approx(2.0)]
    assert gaps[2] == ["engine_step", pytest.approx(1.0)]


def test_prefix_hit_share():
    r = _reader("prefix_hit_share.serve")
    k = {"prefix_hit_tokens": 300, "prefill_tokens": 700,
         "prefix_enabled": True}
    assert r.read({"kind": "serve", "counters": k}) == pytest.approx(30.0)
    assert r.read({"kind": "serve",
                   "counters": {**k, "prefix_enabled": False}}) is None

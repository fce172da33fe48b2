"""The trace reduction on hand-built events and on a recorded trace."""

import time

import pytest

from bench import trace
from bench.trace import DeviceEvent, reduce_events

MS = 1_000_000  # ns


def _ops(*intervals, module="jit_step"):
    return [DeviceEvent(f"op{i}", module, s * MS, (e - s) * MS)
            for i, (s, e) in enumerate(intervals)]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = {"/device:TPU:0": _ops((0, 10), (5, 20), (30, 40), (95, 120))}
    out = reduce_events(ops, {}, [], (0, 100 * MS))
    assert out.window_s == pytest.approx(0.1)
    # [0, 20] + [30, 40] + [95, 100] (clipped at the window's end)
    assert out.busy_s == pytest.approx(0.035)
    assert out.n_devices == 1


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": _ops((0, 50)), "/device:TPU:1": _ops((0, 10))}
    out = reduce_events(ops, {}, [], (0, 100 * MS))
    assert out.busy_s == pytest.approx(0.03)


def test_per_op_and_per_module_device_time():
    ops = {"/device:TPU:0": _ops((0, 10), (20, 25), module="jit_join")}
    mods = {"/device:TPU:0": [
        DeviceEvent("jit_scatter_join(3)", "", 0, 10 * MS),
        DeviceEvent("jit_scatter_join(3)", "", 20 * MS, 5 * MS),
        DeviceEvent("jit_other(1)", "", 50 * MS, 2 * MS)]}
    out = reduce_events(ops, mods, [], (0, 100 * MS))
    assert dict(out.device_ops) == pytest.approx(
        {"jit_join/op0": 0.010, "jit_join/op1": 0.005})
    assert out.device_ops[0][0] == "jit_join/op0"   # the largest first
    assert out.module_seconds("scatter_join") == pytest.approx(0.015)
    assert out.module_seconds("other") == pytest.approx(0.002)


def test_idle_gaps_are_labelled_by_the_innermost_covering_span():
    ops = {"/device:TPU:0": _ops((0, 10), (40, 50), (55, 100))}
    spans = [("bench.window", 0, 100 * MS),
             ("bench.tick", 5 * MS, 60 * MS),
             ("bench.put", 12 * MS, 38 * MS)]
    out = reduce_events(ops, {}, spans, (0, 100 * MS))
    assert out.idle_gaps[0] == ("put", pytest.approx(0.030))
    assert out.idle_gaps[1] == ("tick", pytest.approx(0.005))
    br = out.breakdown()
    assert br["idle_gaps"][0][0] == "put"
    assert br["device_ops"][0][1] == pytest.approx(0.045)


def test_a_gap_outside_every_span_is_named_so():
    ops = {"/device:TPU:0": _ops((0, 10))}
    out = reduce_events(ops, {}, [("bench.window", 0, 50 * MS)],
                        (0, 50 * MS))
    assert out.idle_gaps == [("host:outside-spans", pytest.approx(0.04))]


def test_an_empty_window_is_refused():
    with pytest.raises(ValueError):
        reduce_events({}, {}, [], (5, 5))


def test_a_recorded_trace_has_its_window_and_spans(tmp_path):
    """A trace written by jax.profiler on this host: the reader finds the
    window span; with no TPU plane in it, it refuses to make numbers."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.put"):
            f(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce_xplane(path)


def test_ops_are_labelled_and_placed_in_their_module():
    mods = [DeviceEvent("jit_scatter_join(1234)", "", 100, 50),
            DeviceEvent("jit_get(9)", "", 300, 10)]

    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur
            self.stats = ()

    got = trace._ops_in_modules([
        Ev("%copy.12 = f32[1048576,256]{1,0:T(8,128)} copy(f32[1048576,256]"
           "{1,0:T(8,128)} %vals.1)", 110, 20),
        Ev("%fusion = f32[16]{0} fusion(f32[8] %a), kind=kLoop", 302, 3),
        Ev("unparsed op", 200, 5)], mods)
    assert [(e.name, e.module) for e in got] == [
        ("copy.12:copy", "jit_scatter_join"), ("fusion:fusion", "jit_get"),
        ("unparsed op", "")]

"""Trace reduction: busy time as a union of intervals, op times, idle gaps
tagged with the host span open in them, and the loader on a trace recorded
here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import devtrace
from bench.devtrace import Ev

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur, **stats):
    return Ev(plane, line, name, float(start), float(dur), stats)


def small_trace():
    """A 1000 ns window: ops at [100,300] and [250,400] overlap, [600,700]
    stands alone, [900,1200] runs past the window's end; the host is in a
    step span throughout and enqueues during the gap 400-600."""
    return [
        ev(HOST, "python", "bench/window", 0, 1000),
        ev(HOST, "python", "trainer/step", 0, 1000),
        ev(HOST, "python", "PjRtClient::Execute", 420, 160),
        ev(DEV, "XLA Ops", "sort.1", 100, 200, hlo_category="sort"),
        ev(DEV, "XLA Ops", "fusion.2", 250, 150, hlo_category="loop fusion"),
        ev(DEV, "XLA Ops", "scatter.3", 600, 100),
        ev(DEV, "XLA Ops", "fusion.2", 900, 300, hlo_category="loop fusion"),
        ev(DEV, "XLA Modules", "jit_step", 100, 1100),
        ev("/device:TPU:1", "XLA Ops", "fusion.9", 0, 1000),
    ]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    r = devtrace.reduce(small_trace(), ["0"])
    assert r.window_s == pytest.approx(1000e-9)
    # [100,400] + [600,700] + [900,1000] = 500 ns; the module line does not count
    assert r.busy_s == pytest.approx(500e-9)


def test_busy_averages_over_the_chips_a_cell_uses():
    r = devtrace.reduce(small_trace(), ["0", "1"])
    assert r.busy_s == pytest.approx((500e-9 + 1000e-9) / 2)


def test_op_seconds_by_name_and_category():
    ops = devtrace.reduce(small_trace(), ["0"]).op_seconds()
    assert ops["sort.1 [sort]"] == pytest.approx(200e-9)
    assert ops["fusion.2 [loop fusion]"] == pytest.approx(250e-9)   # 150 + 100 clipped
    assert ops["scatter.3"] == pytest.approx(100e-9)


def test_gaps_are_tagged_with_what_the_host_was_doing():
    gaps = devtrace.reduce(small_trace(), ["0"]).gaps()
    assert [round(s * 1e9) for _, s in gaps] == [200, 200, 100]     # longest first
    assert gaps[0][0] == "trainer/step | PjRtClient::Execute"
    assert gaps[2][0] == "trainer/step | trainer/step"
    assert len(devtrace.reduce(small_trace(), ["0"]).gaps(longest=1)) == 1
    bd = devtrace.reduce(small_trace(), ["0"]).breakdown()
    assert bd["device_ops"][0][0] == "fusion.2 [loop fusion]"
    assert len(bd["idle_gaps"]) <= 10 and bd["idle_gaps"][0][1] == pytest.approx(200e-9)


def test_no_window_or_no_device_op_reads_nothing():
    assert devtrace.reduce([e for e in small_trace() if e.name != "bench/window"], ["0"]) is None
    assert devtrace.reduce([e for e in small_trace() if e.plane != DEV], ["0"]) is None


def test_loader_reads_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sort(x, axis=0) * 2.0)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.load(str(tmp_path))
    win = [e for e in events if e.name == "bench/window"]
    assert len(win) == 1 and win[0].dur > 0 and win[0].plane.startswith("/host:")
    dump = devtrace.dump(events)
    assert any(k.startswith("/host:CPU") for k in dump)

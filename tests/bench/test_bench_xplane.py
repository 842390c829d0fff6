"""The trace reduction (``bench/xplane.py``): interval arithmetic on known
intervals, and the whole reduction on a trace recorded here on the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import xplane


def test_merge_and_gaps():
    busy = xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert xplane.gaps(busy, 1, 10) == [(3, 5), (8, 10)]
    assert xplane.gaps(busy, 0, 3) == []


@pytest.mark.parametrize("lo,hi,want", [
    (0, 10, 5.0), (1, 6, 3.0), (3, 5, 0.0), (6, 7, 1.0), (-5, 0.5, 0.5),
    (9, 12, 0.0)])
def test_coverage(lo, hi, want):
    cover = xplane.coverage(xplane.merge([(0, 3), (5, 7)]))
    assert cover(lo, hi) == pytest.approx(want)


def test_innermost_picks_the_latest_started_covering_event():
    events = [("call", 0, 10), ("dispatch", 2, 4), ("get", 6, 9),
              ("call", 20, 30)]
    assert xplane.innermost(events, [1, 3, 5, 7, 15, 25]) == [
        "call", "dispatch", "call", "get", None, "call"]


def test_summarize_names_gaps_by_span():
    device = [[("op_a", 10, 20), ("op_b", 30, 35), ("op_a", 50, 60)]]
    host = [[("bench.window", 0, 100), ("bench.study", 12, 45),
             ("dispatch", 21, 29), ("bench.study", 46, 95)]]
    got = xplane.summarize(device, host)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(25e-9)
    assert got["device_ops"][0] == ["op_a", pytest.approx(20e-9)]
    idle = dict((n, t) for n, t in got["idle_gaps"])
    assert idle["study: dispatch"] == pytest.approx(10e-9)
    assert idle["study"] == pytest.approx(15e-9 + 40e-9)
    assert idle["between calls"] == pytest.approx(10e-9)
    assert [c[1] for c in got["calls"]] == [pytest.approx(13e-9),
                                            pytest.approx(10e-9)]


def test_reduce_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.study"):
                f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    got = xplane.reduce(str(tmp_path))
    assert 0 < got["busy_s"] < got["window_s"]
    assert len(got["calls"]) == 3
    assert all(busy <= span for span, busy in got["calls"])
    idle = dict((n, t) for n, t in got["idle_gaps"])
    # the sleeps between calls are idle time outside every call span
    assert idle["between calls"] >= 0.04
    assert sum(idle.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)

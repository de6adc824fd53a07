"""The reduction from the profiler's trace to busy union, idle share, time by
operation and program and the attribution of gaps, on ``small_trace.textproto``: every
expected number is worked out by hand from the times written in that file."""

import os
import types

import pytest
from jax.profiler import ProfileData

from readers import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
#: the window annotation opens at trace time 1000 ns and at perf_counter 0
OFFSET_NS = 1000.0
#: (name, start_s, end_s) on perf_counter: trace 1000..6200, 6200..7100,
#: 7100..10200, 10200..11000
HOST_SPANS = [("bench.round", 0.0, 5.2e-6), ("bench.wait", 5.2e-6, 6.1e-6),
              ("bench.round", 6.1e-6, 9.2e-6), ("bench.wait", 9.2e-6, 10.0e-6)]


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "small_trace.textproto")) as f:
        text = "\n".join(line.split("#")[0] for line in f.read().splitlines())
    return xplane.Trace(ProfileData.from_text_proto(text))


def test_window_and_busy_union(trace):
    assert trace.window == (1000.0, 11000.0)
    assert trace.window_s == pytest.approx(10000e-9)
    # [2000, 6000] (the while and fusion.3 touch), [7000, 9000], [9500, 10000]
    assert trace.busy_s() == pytest.approx(6500e-9)
    assert trace.gaps() == [(1000.0, 2000.0), (6000.0, 7000.0), (9000.0, 9500.0),
                            (10000.0, 11000.0)]


def test_self_time_and_programs(trace):
    by_op = dict(map(tuple, trace.time_by_op()))
    # the while spans 3000 ns of which its two children cover 2000
    assert by_op["while.1"] == pytest.approx(1000e-9)
    assert by_op["fusion.2"] == pytest.approx(3000e-9)
    assert next(iter(by_op)) == "fusion.2"            # the longest first
    assert trace.module_times("jit_round_fn*") == pytest.approx([4000e-9, 3000e-9])


def test_gaps_go_to_the_open_host_span(trace):
    rows = dict(map(tuple, trace.gaps_by_host_span(HOST_SPANS, OFFSET_NS)))
    # 1000..2000 and 9000..9500 fall in a round; 6000..7000 and 10000..11000
    # in a wait
    assert rows == {"bench.wait": pytest.approx(2000e-9),
                    "bench.round": pytest.approx(1500e-9)}
    assert dict(map(tuple, trace.gaps_by_host_span([], OFFSET_NS))) == \
        {"(no span)": pytest.approx(3500e-9)}


def test_readers(trace):
    run = types.SimpleNamespace(trace=trace, host_spans=HOST_SPANS,
                                clock_offset_ns=OFFSET_NS, peak={}, cfg={}, cell={},
                                counters={})
    assert xplane.read({"kind": "idle_pct"}, run) == pytest.approx(35.0)
    # round 1: 5200 long, 4000 busy inside; round 2: 3100 long, 2400 busy
    assert xplane.read({"kind": "span_minus_busy_ms", "span": "bench.round"}, run) == \
        pytest.approx((1200 + 700) / 2 / 1e6)
    assert xplane.read({"kind": "span_minus_busy_ms", "span": "absent"}, run) is None
    run.trace = None
    assert xplane.read({"kind": "idle_pct"}, run) is None

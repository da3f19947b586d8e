"""The trace reduction: busy time as the union of device operations,
idle share, time by kernel and by program, idle gaps given to the host
span they fall in.  Checked on a hand-made trace and on a slice of a
trace the profiler recorded (``data/trace_small.json``, whose
``source`` names where), against values a separate sweep over its
interval ends computed."""
import json
import os

import pytest

from harness import trace as TRC

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _hand():
    # window 0..100 ns; ops overlap (10..30 with 20..40), one op runs
    # past the window's end; host spans: a tick 0..60 holding a fetch
    # 45..60, a wait 60..100
    return TRC.Trace(
        ops=[("fusion.1", 10, 30, "jit__tick"),
             ("flash_attention", 20, 40, "jit__tick"),
             ("fusion.2", 50, 55, "jit__tick"),
             ("copy", 90, 120, "jit__admit")],
        spans=[(TRC.WINDOW, 0, 100), ("bench.tick", 0, 60),
               ("bench.fetch", 45, 60), ("bench.wait", 60, 100)])


def test_busy_is_the_union_of_device_operations():
    r = TRC.Reduced(_hand())
    # 10..40 (30) + 50..55 (5) + 90..100 (10, clipped)
    assert r.busy_s == pytest.approx(45e-9)
    assert r.window_s == pytest.approx(100e-9)
    assert r.idle_share() == pytest.approx(0.55)


def test_time_by_kernel_and_by_program():
    r = TRC.Reduced(_hand())
    assert r.op_seconds("flash_attention") == pytest.approx(20e-9)
    # a program's time is the union of its operations' intervals: an
    # event that holds others (a loop) counts once
    assert r.op_seconds("jit__tick") == pytest.approx(35e-9)
    assert r.op_seconds("jit__admit") == pytest.approx(10e-9)
    assert r.op_count("fusion") == 2
    top = r.top_ops(2)
    assert sorted(n for n, _ in top) == ["flash_attention", "fusion.1"]
    assert [t for _, t in top] == [pytest.approx(20e-9)] * 2


def test_idle_gaps_go_to_the_innermost_host_span():
    r = TRC.Reduced(_hand())
    got = dict((n, t) for n, t in r.idle_by_span())
    # gaps: 0..10 (tick), 40..50 (tick; midpoint 45 is inside the fetch
    # too, and the fetch is innermost), 55..90 (midpoint 72.5: wait)
    assert got["bench.tick"] == pytest.approx(10e-9)
    assert got["bench.fetch"] == pytest.approx(10e-9)
    assert got["bench.wait"] == pytest.approx(35e-9)
    inside, idle = r.span_idle("bench.tick")
    assert inside == pytest.approx(60e-9)
    assert idle == pytest.approx(25e-9)


def test_one_window_span_is_required():
    tr = _hand()
    tr.spans = [s for s in tr.spans if s[0] != TRC.WINDOW]
    with pytest.raises(ValueError):
        TRC.Reduced(tr)


def test_recorded_trace():
    path = os.path.join(DATA, "trace_small.json")
    with open(path) as f:
        d = json.load(f)
    r = TRC.Reduced(TRC.Trace.from_json(d))
    exp = d["expected"]
    assert r.busy_s == pytest.approx(exp["busy_s"], rel=1e-9)
    assert r.window_s == pytest.approx(exp["window_s"], rel=1e-9)
    for needle, secs in exp["op_seconds"].items():
        assert r.op_seconds(needle) == pytest.approx(secs, rel=1e-9)
    inside, idle = r.span_idle("bench.tick")
    assert idle == pytest.approx(exp["tick_idle_s"], rel=1e-9)
    assert 0 < r.busy_s <= r.window_s

"""Trace reduction: busy union, time per kind of operation, scatter share,
idle gaps joined to host events; on a synthetic trace and on one recorded
on a v5e chip (one warm scale-19 R-MAT solve, source paths removed)."""
import gzip
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import trace  # noqa: E402

RECORDED = os.path.join(BENCH, "tests", "data", "rmat19_solve.trace.json.gz")
HOST, DEV0, DEV1 = 701, 3, 4


def meta(pid, name, threads):
    out = [{"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}]
    out += [{"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
             "args": {"name": tname}} for tid, tname in threads.items()]
    return out


def ev(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def synthetic(extra=()):
    """Window 1000-11000 us on the host thread 9."""
    events = (meta(HOST, "/host:CPU", {9: "python3", 10: "pjrt"})
              + meta(DEV0, "/device:TPU:0", {3: "XLA Ops", 2: "XLA Modules"})
              + [
                  ev(HOST, 9, "bench.window", 1000, 10000),
                  ev(HOST, 9, "bench.solve", 1000, 6000),
                  ev(HOST, 9, "PjitFunction(interval)", 1500, 500),
                  ev(HOST, 9, "device_get", 5000, 1500),
                  ev(HOST, 9, "bench.solve", 7000, 4000),
                  ev(HOST, 10, "other thread", 0, 20000),
                  # starts before the window: clipped to 1000-1500
                  ev(DEV0, 3, "fusion.28", 0, 1500,
                     tf_op="jit(<unknown>)/while/body/jit(segment_min64)/"
                           "scatter-min:"),
                  ev(DEV0, 3, "while.1", 1900, 1700, hlo_category="while"),
                  ev(DEV0, 3, "fusion.12", 2000, 1000,
                     tf_op="jit(<unknown>)/while/body/gather:"),
                  ev(DEV0, 3, "fusion.13", 2500, 1000,
                     tf_op="jit(<unknown>)/while/body/gather:"),
                  ev(DEV0, 3, "sort.1", 8000, 1000, hlo_category="sort"),
                  ev(DEV0, 3, "fusion.7", 9500, 500,
                     long_name="%fusion.7 = u64[8] scatter(u64[8] %a)"),
                  ev(DEV0, 3, "copy.1", 20000, 100),   # after the window
                  ev(DEV0, 2, "jit_interval", 0, 20000),
              ])
    return {"traceEvents": events + list(extra)}


def test_busy_union_and_window():
    s = trace.reduce_trace(synthetic())
    assert s.window_s == pytest.approx(10000e-6)
    # 1000-1500, 1900-3600 (the while and the gathers in it), 8000-9000,
    # 9500-10000: 500 + 1700 + 1000 + 500 us
    assert s.busy_s == pytest.approx(3700e-6)
    assert s.idle_share == pytest.approx(1 - 0.37)
    assert s.chips == 1 and s.ops == 6


def test_kinds_and_scatter_share():
    s = trace.reduce_trace(synthetic())
    assert s.op_s["segment_min64/scatter-min"] == pytest.approx(500e-6)
    assert s.op_s["gather"] == pytest.approx(2000e-6)
    assert s.op_s["sort"] == pytest.approx(1000e-6)
    assert "while" not in s.op_s                # its body counts instead
    assert s.scatter_s == pytest.approx(1000e-6)  # scatter-min + fusion.7


def test_idle_gaps_take_innermost_host_event():
    s = trace.reduce_trace(synthetic())
    gaps = {}
    for name, sec in s.gaps:
        gaps[name] = gaps.get(name, 0.0) + sec
    # Idle 1500-1900, 3600-8000, 9000-9500 and 10000-11000; each goes to
    # the innermost event of the window's thread over its middle (1700,
    # 5800, 9250, 10500), never to another thread's.
    assert gaps == pytest.approx({"PjitFunction(interval)": 400e-6,
                                  "device_get": 4400e-6,
                                  "bench.solve": 1500e-6})
    bd = s.breakdown()
    assert bd["idle_gaps"][0][0] == "device_get"
    assert bd["device_ops"][0] == ["gather", pytest.approx(2000e-6)]


def test_busy_averages_over_chips():
    second = meta(DEV1, "/device:TPU:1", {3: "XLA Ops"}) + [
        ev(DEV1, 3, "fusion.1", 1000, 7000)]
    s = trace.reduce_trace(synthetic(second))
    assert s.chips == 2
    assert s.busy_s == pytest.approx((3700e-6 + 7000e-6) / 2)


def test_missing_window_or_ops_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_trace({"traceEvents": meta(DEV0, "/device:TPU:0", {})})
    bare = meta(HOST, "/host:CPU", {9: "python3"}) + [
        ev(HOST, 9, "bench.window", 0, 10)]
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce_trace({"traceEvents": bare})


@pytest.mark.parametrize("event,kind", [
    ({"name": "fusion.3", "args": {}}, "fusion"),
    ({"name": "%all-reduce.1.2 = u32[8] all-reduce(...)"}, "all-reduce"),
    ({"name": "x", "args": {"tf_op": "jit(<unknown>)/while/body/gather:"}},
     "gather"),
    ({"name": "x", "args": {"tf_op": "jit(a)/jit(b)/scatter-min:"}},
     "b/scatter-min"),
])
def test_op_kind(event, kind):
    assert trace.op_kind(event) == kind


def test_recorded_chip_trace():
    """One warm scale-19 solve on a v5e: the segment-min scatter takes
    most of the device time, and the device is seldom idle."""
    with gzip.open(RECORDED, "rt") as f:
        s = trace.reduce_trace(json.load(f))
    assert s.chips == 1 and s.ops > 1000
    assert s.window_s == pytest.approx(24.69, abs=0.01)
    assert 0.95 < s.busy_s / s.window_s <= 1.0
    assert 0.6 < s.scatter_s / s.busy_s < 0.8
    top = s.breakdown()["device_ops"][0]
    assert top[0] == "segment_min64/scatter-min" and top[1] > 15.0

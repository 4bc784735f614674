"""Reading the program's own tracing out of a trace: device seconds per
phase scope, host spans, chip 0's busy time inside them, and the numbers
built from those; on a synthetic trace, and on the cell traces recorded on
a v5e chip."""
import gzip
import json
import os
import random
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import phases, trace  # noqa: E402

import trace_split  # noqa: E402

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


BODY = "jit(<unknown>)/while/body/"


def synthetic():
    """Window 1000-11000 us on host thread 9: two flushes with their
    children, scoped and unscoped device ops."""
    return {"traceEvents": (
        meta(HOST, "/host:CPU", {9: "python3", 10: "pjrt"})
        + meta(DEV0, "/device:TPU:0", {3: "XLA Ops"})
        + [
            ev(HOST, 9, "bench.window", 1000, 10000),
            ev(HOST, 9, "bench.poll", 1900, 2200),
            ev(HOST, 9, "serve.flush", 2000, 2000, trigger="deadline"),
            ev(HOST, 9, "serve.pack", 2000, 300),
            ev(HOST, 9, "serve.solve", 2300, 1500),
            ev(HOST, 9, "interval.readback", 2600, 1100),
            ev(HOST, 9, "serve.complete", 3800, 200),
            ev(HOST, 9, "serve.flush", 6000, 1000, trigger="size"),
            ev(HOST, 9, "serve.solve", 6100, 800),
            ev(HOST, 9, "serve.flush", 10900, 500),    # leaves the window
            ev(HOST, 10, "serve.flush", 1000, 9000),   # another thread
            ev(DEV0, 3, "while.1", 2400, 1200, hlo_category="while"),
            ev(DEV0, 3, "fusion.1", 2500, 500,
               tf_op=BODY + "election/gather:"),
            ev(DEV0, 3, "fusion.2", 3000, 500,
               tf_op=BODY + "vmap(merge)/while/body/scatter-min:"),
            ev(DEV0, 3, "fusion.3", 6200, 200,
               tf_op=BODY + "record/scatter:"),
            ev(DEV0, 3, "fusion.4", 8000, 1000, tf_op="key:"),
            ev(DEV0, 3, "fusion.5", 500, 1000,          # clipped to 1000
               tf_op=BODY + "election/jit(segment_min64)/scatter-min:"),
        ])}


def test_scope_seconds_and_op_total():
    ph = phases.reduce_phases(synthetic())
    assert ph.scope_s == pytest.approx({
        "election": 1000e-6, "record": 200e-6, "merge": 500e-6,
        "census": 0.0, "compact": 0.0})
    assert ph.op_s == pytest.approx(2700e-6)         # the while not counted
    assert phases.phase_share(ph, "election") == pytest.approx(100 / 2.7)
    assert phases.phase_share(ph, "merge") == pytest.approx(50 / 2.7)


def test_spans_and_busy_intervals_of_the_window():
    ph = phases.reduce_phases(synthetic())
    assert ph.spans["serve.flush"] == [(2000, 2000), (6000, 1000)]
    assert ph.spans["serve.pack"] == [(2000, 300)]
    assert ph.spans["bench.poll"] == [(1900, 2200)]
    assert "bench.window" not in ph.spans
    assert ph.busy0 == [[1000, 1500], [2400, 3600], [6200, 6400],
                        [8000, 9000]]


def test_flush_numbers():
    ph = phases.reduce_phases(synthetic())
    assert phases.flush_ms(ph) == pytest.approx(2.0)
    # busy 1200 us in the first flush, 200 in the second, of 3000 us
    assert phases.flush_device_share(ph) == pytest.approx(1400 / 30)
    split = phases.split(ph, 2)
    assert "bench.poll" not in split
    assert split["serve.solve"] == pytest.approx(
        {"count": 1.0, "ms": 1.15, "busy_ms": (1200 + 200) / 2e3})
    assert split["interval.readback"]["busy_ms"] == pytest.approx(0.5)


def test_queue_wait_is_a_mean_over_completed_requests():
    assert phases.queue_wait_ms({"queue_wait_s": 0.09, "completed": 2}) \
        == pytest.approx(45.0)
    assert phases.queue_wait_ms({"completed": 2}) is None
    assert phases.queue_wait_ms({"queue_wait_s": 0.0, "completed": 0}) \
        is None


def test_a_trace_without_spans_or_flushes_reads_nothing():
    t = synthetic()
    t["traceEvents"] = [e for e in t["traceEvents"]
                        if not e["name"].startswith("serve.")]
    ph = phases.reduce_phases(t)
    assert phases.flush_ms(ph) is None
    assert phases.flush_device_share(ph) is None


@pytest.mark.parametrize("event,phase", [
    ({"args": {"tf_op": BODY + "election/gather:"}}, "election"),
    ({"args": {"tf_op": BODY + "vmap(compact)/sort"}}, "compact"),
    ({"args": {"tf_op": "jit(<unknown>)/census/gather"}}, "census"),
    ({"args": {"tf_op": BODY + "gather"}}, None),
    ({"args": {"tf_op": "election"}}, None),        # the op itself
    ({"name": "fusion.3", "args": {}}, None),
])
def test_phase_of(event, phase):
    assert phases.phase_of(event) == phase


def test_busy_meter_matches_a_plain_sum():
    rnd = random.Random(5)
    busy = trace._union([(s, s + rnd.uniform(0, 30))
                         for s in sorted(rnd.uniform(0, 1000)
                                         for _ in range(200))])
    within = phases.busy_meter(busy)
    for _ in range(300):
        a = rnd.uniform(-50, 1050)
        b = a + rnd.uniform(0, 300)
        plain = sum(max(0.0, min(e, b) - max(s, a)) for s, e in busy)
        assert within(a, b) == pytest.approx(plain, abs=1e-9)


def test_cut_keeps_the_window_and_the_read_arguments():
    t = synthetic()
    t["traceEvents"][-1]["args"]["long_name"] = "%fusion.5 = ..."
    window = [e for e in t["traceEvents"] if e["name"] == "bench.window"]
    kept = trace_split.cut(t, window[0])
    names = [e["name"] for e in kept["traceEvents"] if e["ph"] == "X"]
    assert "fusion.5" in names and "fusion.4" in names
    assert not any(e.get("tid") == 10 for e in kept["traceEvents"]
                   if e["ph"] == "X")               # another host thread
    assert all("long_name" not in e.get("args", {})
               for e in kept["traceEvents"])
    flush = [e for e in kept["traceEvents"] if e["name"] == "serve.flush"]
    assert flush[0]["args"] == {"trigger": "deadline"}
    assert phases.reduce_phases(kept).scope_s == \
        phases.reduce_phases(t).scope_s
    assert trace.reduce_trace(kept).op_s == trace.reduce_trace(t).op_s


RECORDED = os.path.join(BENCH, "tests", "data",
                        "tenants_poisson.trace.json.gz")


def test_recorded_serve_trace():
    """0.6 s of ``tenants.poisson`` traced on a v5e (``trace_split.py
    --keep``): every flush carries its trigger and its children, nearly
    all device time sits under a phase scope, and the accepted reduction
    still reads it, with program spans where the poll loop was."""
    with gzip.open(RECORDED, "rt") as f:
        raw = json.load(f)
    ph = phases.reduce_phases(raw)
    flushes = ph.spans["serve.flush"]
    assert len(flushes) == 23
    for child in ("serve.pack", "serve.solve", "serve.complete",
                  "msf.stage", "interval.readback", "msf.fetch",
                  "msf.forest"):
        assert len(ph.spans[child]) == len(flushes), child
    assert len(ph.spans["interval.dispatch"]) == 2 * len(flushes)
    triggers = {e["args"].get("trigger") for e in raw["traceEvents"]
                if e.get("name") == "serve.flush"}
    assert triggers == {"deadline"}
    assert sum(ph.scope_s.values()) / ph.op_s > 0.95
    assert ph.scope_s["election"] > 0 and ph.scope_s["merge"] > 0
    assert 20 < phases.flush_device_share(ph) < 50
    assert 5 < phases.flush_ms(ph) < 30
    s = trace.reduce_trace(raw)
    assert s.breakdown()["device_ops"][0][0] == "gather"
    gaps = dict(s.breakdown(top=50)["idle_gaps"])
    assert gaps.get("bench.poll", 0.0) < gaps["msf.stage"]

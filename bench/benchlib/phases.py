"""Read the program's own tracing out of a profiler trace.

:mod:`benchlib.trace` reduces a trace to what the accepted per-layer
metrics read.  The program also marks its work itself, and this module
reads those marks from the same Chrome-trace dict, inside the same
``bench.window`` span:

* device phases: the round bodies run under ``jax.named_scope`` names,
  which every device op carries as a component of its ``tf_op``
  (``jit(<unknown>)/while/body/election/jit(segment_min64)/scatter-min``;
  under ``vmap`` the component reads ``vmap(election)``);
* host spans: ``jax.profiler.TraceAnnotation`` spans of the program
  (``serve.*``, ``msf.*``, ``interval.*``) on the benchmark's thread, on
  the device trace's clock.

:func:`reduce_phases` gives, for one window, the device seconds per phase
and of all operations (chip means; control-flow containers count toward
neither, as in :mod:`benchlib.trace`), the host spans by name, and chip
0's busy intervals.  The functions below it turn those, and the service's
counters, into the split of a solve by phase and of a flush by child span.
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools

from benchlib import stats
from benchlib import trace as trace_lib

PHASES = ("election", "record", "merge", "census", "compact")
FLUSH = "serve.flush"


@dataclasses.dataclass
class Phases:
    scope_s: dict       # phase -> device seconds, mean over chips
    op_s: float         # device seconds of all operations, mean over chips
    spans: dict         # host span name -> [(start_us, dur_us)], window
    busy0: list         # chip 0's merged busy intervals [[start, end]] us


def phase_of(event: dict):
    """The phase scope a device op ran under, or ``None``."""
    tf_op = str(event.get("args", {}).get("tf_op", "")).rstrip(":")
    found = None
    for part in tf_op.split("/")[:-1]:
        if part.startswith("vmap(") and part.endswith(")"):
            part = part[5:-1]
        if part in PHASES:
            found = part
    return found


def reduce_phases(trace: dict) -> Phases:
    events = trace["traceEvents"]
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    windows = [ev for ev in events if ev.get("ph") == "X"
               and ev.get("name") == trace_lib.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {trace_lib.WINDOW_SPAN!r} span")
    win = windows[0]
    lo, hi = win["ts"], win["ts"] + win["dur"]

    spans: dict = {}
    ops_by_chip: dict = {}
    for ev in events:
        if ev.get("ph") != "X" or ev is win:
            continue
        s, e = ev["ts"], ev["ts"] + ev.get("dur", 0.0)
        if ev["pid"] == win["pid"] and ev.get("tid") == win["tid"]:
            if lo <= s and e <= hi:
                spans.setdefault(ev["name"], []).append((s, e - s))
            continue
        proc = procs.get(ev["pid"], "")
        if not proc.startswith("/device:TPU") or \
                threads.get((ev["pid"], ev.get("tid"))) != \
                trace_lib.OPS_THREAD:
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            ops_by_chip.setdefault(proc, []).append((s, e, ev))
    if not ops_by_chip:
        raise ValueError("trace holds no device operation in the window")

    scope_us = dict.fromkeys(PHASES, 0.0)
    op_us, busy0 = 0.0, None
    for chip in sorted(ops_by_chip):
        ops = ops_by_chip[chip]
        if busy0 is None:
            busy0 = trace_lib._union([(s, e) for s, e, _ in ops])
        for s, e, ev in ops:
            if trace_lib._is_container(ev):
                continue
            op_us += e - s
            phase = phase_of(ev)
            if phase is not None:
                scope_us[phase] += e - s
    chips = len(ops_by_chip)
    return Phases(scope_s={k: v * 1e-6 / chips for k, v in scope_us.items()},
                  op_s=op_us * 1e-6 / chips, spans=spans, busy0=busy0)


def phase_share(ph: Phases, phase: str):
    """Percent of the device's operation time spent in ``phase``."""
    if ph.op_s <= 0:
        return None
    return 100.0 * ph.scope_s[phase] / ph.op_s


def busy_meter(busy: list):
    """``f(start, end)``: microseconds of ``busy`` (sorted, disjoint
    intervals) inside [start, end], in logarithmic time."""
    starts = [s for s, _ in busy]
    ends = [e for _, e in busy]
    before = [0.0] + list(itertools.accumulate(e - s for s, e in busy))

    def within(start: float, end: float) -> float:
        i = bisect.bisect_right(ends, start)     # first ending after start
        j = bisect.bisect_left(starts, end)      # first starting at/after end
        if j <= i:
            return 0.0
        total = before[j] - before[i]
        total -= max(0.0, start - starts[i])     # clip the first
        total -= max(0.0, ends[j - 1] - end)     # and the last
        return total

    return within


def flush_ms(ph: Phases):
    """95th percentile (nearest rank) of the window's flush durations."""
    durs = [d * 1e-3 for _, d in ph.spans.get(FLUSH, [])]
    return stats.percentile(durs, 95) if durs else None


def flush_device_share(ph: Phases):
    """Percent of the time inside flushes in which chip 0 was busy: low
    means the host bounds a flush."""
    flushes = ph.spans.get(FLUSH, [])
    total = sum(d for _, d in flushes)
    if total <= 0:
        return None
    within = busy_meter(ph.busy0)
    return 100.0 * sum(within(s, s + d) for s, d in flushes) / total


def queue_wait_ms(counters: dict):
    """Mean milliseconds a completed request waited in its queue for the
    flush that took it, from the service's counters over the window."""
    done = counters.get("completed", 0)
    if not done or "queue_wait_s" not in counters:
        return None
    return 1e3 * counters["queue_wait_s"] / done


def split(ph: Phases, per: int) -> dict:
    """For every program span (``serve.*``, ``msf.*``, ``interval.*``): how
    many, and the milliseconds they and chip 0's busy time inside them
    took, each per one of ``per`` units of work (the window's solves or
    flushes)."""
    within = busy_meter(ph.busy0)
    out = {}
    for name, found in sorted(ph.spans.items()):
        if name.split(".")[0] not in ("serve", "msf", "interval"):
            continue
        out[name] = {
            "count": len(found) / per,
            "ms": sum(d for _, d in found) * 1e-3 / per,
            "busy_ms": sum(within(s, s + d) for s, d in found) * 1e-3 / per}
    return out

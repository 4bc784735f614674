"""Reduce a profiler trace to the numbers the per-layer metrics read.

``jax.profiler.stop_trace`` writes, under
``<dir>/plugins/profile/<run>/``, an ``.xplane.pb`` and the same trace as
Chrome trace JSON (``<host>.trace.json.gz``).  The JSON is read here: it
carries, for each device operation, the HLO category and the JAX
operation that emitted it (``tf_op``, such as
``jit(<unknown>)/while/body/jit(segment_min64)/scatter-min``), which the
raw event stats of the ``.xplane.pb`` lack.  Reading it needs only
``gzip`` and ``json``.

Processes are named by metadata events: devices ``/device:TPU:<i>`` with
a thread ``XLA Ops``, and the host ``/host:CPU`` with one thread per host
thread, where the benchmark's own spans (``jax.profiler.TraceAnnotation``)
and JAX's dispatch events sit on the same clock (microseconds).

:func:`reduce_trace` reads, inside the window the benchmark marked with
its ``bench.window`` span:

* per chip, the union of the intervals in which an operation ran (busy);
* the device time of each kind of operation (by the JAX operation that
  emitted it), and of scatter operations;
* the idle gaps of the first chip, each put down to the innermost host
  event of the benchmark's thread that covers the gap's middle.

Control-flow operations (``while``, ``conditional``, ``call``) count
toward busy time but not toward any kind, since their bodies' operations
are events of their own.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

WINDOW_SPAN = "bench.window"
OPS_THREAD = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"[.:_-]?\d+$")


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # length of the traced window
    busy_s: float                   # mean over chips of the busy union
    chips: int                      # device processes with operations
    op_s: dict                      # kind of operation -> device seconds
    scatter_s: float                # mean over chips of scatter seconds
    gaps: list                      # [(host event name, seconds)], chip 0
    ops: int                        # operation events in the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        by_host: dict = {}
        for name, s in self.gaps:
            by_host[name] = by_host.get(name, 0.0) + s
        gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def op_kind(event: dict) -> str:
    """The JAX operation that emitted a device op, with the innermost named
    ``jit`` around it: ``segment_min64/scatter-min``.  Without that
    metadata, the HLO name without its number: ``fusion.3`` -> ``fusion``.
    """
    tf_op = str(event.get("args", {}).get("tf_op", "")).rstrip(":")
    if tf_op:
        parts = tf_op.split("/")
        scopes = [p[4:-1] for p in parts[:-1]
                  if p.startswith("jit(") and p != "jit(<unknown>)"]
        return "/".join(scopes[-1:] + [parts[-1]])
    base = str(event.get("name", "")).lstrip("%").split(" ")[0]
    while True:
        stripped = _SUFFIX.sub("", base)
        if stripped == base or not stripped:
            return base
        base = stripped


def is_scatter(event: dict) -> bool:
    """An XLA scatter (any combiner), alone or fused."""
    args = event.get("args", {})
    prim = str(args.get("tf_op", "")).rstrip(":").split("/")[-1]
    if prim.startswith("scatter"):
        return True
    if "scatter" in str(args.get("hlo_category", "")).lower():
        return True
    return bool(re.search(r"=\s*[^=]*?\bscatter\(",
                          str(args.get("long_name", ""))))


def _is_container(event: dict) -> bool:
    category = str(event.get("args", {}).get("hlo_category", ""))
    return category in CONTAINERS or op_kind(event) in CONTAINERS


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _innermost(host_events, times) -> list:
    """Name of the innermost host event covering each of ``times``.

    Events of one thread nest, so a sweep with a stack of open events
    answers every query in one pass: after pushing all events that start
    at or before ``t`` and popping those that ended before it, the top of
    the stack is the innermost event that covers ``t``."""
    events = sorted(host_events, key=lambda ev: (ev[1], -ev[2]))
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = [""] * len(times)
    stack, j = [], 0
    for i in order:
        t = times[i]
        while j < len(events) and events[j][1] <= t:
            while stack and stack[-1][2] < events[j][1]:
                stack.pop()
            stack.append(events[j])
            j += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out[i] = stack[-1][0] if stack else "(no host event)"
    return out


def reduce_trace(trace: dict) -> TraceSummary:
    """Reduce a Chrome-trace dict (``traceEvents``) as described above."""
    events = trace["traceEvents"]
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    spans = [ev for ev in events
             if ev.get("ph") == "X" and ev.get("name") == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    win = spans[0]
    lo, hi = win["ts"], win["ts"] + win["dur"]
    host_events = [(ev["name"], ev["ts"], ev["ts"] + ev.get("dur", 0.0))
                   for ev in events
                   if ev.get("ph") == "X" and ev is not win
                   and ev["pid"] == win["pid"] and ev["tid"] == win["tid"]
                   and ev["ts"] < hi and ev["ts"] + ev.get("dur", 0.0) > lo]
    ops_by_chip: dict = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        proc = procs.get(ev.get("pid"), "")
        if not proc.startswith("/device:TPU") or \
                threads.get((ev["pid"], ev.get("tid"))) != OPS_THREAD:
            continue
        s, e = max(ev["ts"], lo), min(ev["ts"] + ev.get("dur", 0.0), hi)
        if e > s:
            ops_by_chip.setdefault(proc, []).append((s, e, ev))
    if not ops_by_chip:
        raise ValueError("trace holds no device operation in the window")

    busy, op_s, scatter_us, gaps, n_ops = [], {}, 0.0, [], 0
    for chip in sorted(ops_by_chip):
        ops = ops_by_chip[chip]
        merged = _union([(s, e) for s, e, _ in ops])
        busy.append(sum(e - s for s, e in merged))
        for s, e, ev in ops:
            n_ops += 1
            if _is_container(ev):
                continue
            k = op_kind(ev)
            op_s[k] = op_s.get(k, 0.0) + (e - s) * 1e-6
            if is_scatter(ev):
                scatter_us += e - s
        if len(busy) == 1:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s]
            names = _innermost(host_events, [(s + e) / 2 for s, e in idle])
            gaps = [(name, (e - s) * 1e-6)
                    for name, (s, e) in zip(names, idle)]
    return TraceSummary(
        window_s=(hi - lo) * 1e-6,
        busy_s=sum(busy) / len(busy) * 1e-6,
        chips=len(busy),
        op_s=op_s,
        scatter_s=scatter_us * 1e-6 / len(busy),
        gaps=gaps,
        ops=n_ops)


def read_dir(trace_dir: str) -> TraceSummary:
    """Reduce the one Chrome trace the profiler wrote under
    ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
    if len(found) != 1:
        raise ValueError(f"expected one .trace.json.gz under {trace_dir}, "
                         f"found {found}")
    with gzip.open(found[0], "rt") as f:
        return reduce_trace(json.load(f))

#!/usr/bin/env python3
"""Split a cell's window by the program's own spans and phase scopes, on
the chip, and measure what the tracing costs.

    python3 bench/trace_split.py --workload tenants.poisson --seed 7 \\
        --seconds 10 [--untraced 2] [--keep window.trace.json.gz]

One process sets the cell up once, runs ``--untraced`` windows with no
profiler session, then one window under the profiler, and prints a JSON
line per window: the end-to-end metrics, and for the traced window what
:mod:`benchlib.phases` reads (device seconds per phase scope and their
shares of all operation time, the flush's p95 and device share, the mean
queue wait, and every program span per solve or per flush with chip 0's
busy time inside it) beside :mod:`benchlib.trace`'s breakdown.  The last
line gives the host cost of one span enter and exit with no profiler
session.  ``--keep`` writes the traced window's Chrome trace, cut to what
the readers read (:func:`cut`).  The benchmark's own runs
(``bench/run.py``) never use this script.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from benchlib import harness, phases, registry  # noqa: E402
from benchlib import trace as trace_lib  # noqa: E402

SERVE_COUNTERS = ("queue_wait_s", "completed")
KEEP_ARGS = ("tf_op", "hlo_category", "trigger", "n_pad", "cap", "occupied",
             "lanes")


def _counters(drv) -> dict:
    svc = getattr(drv, "service", None)
    if svc is None:
        return {}
    return {k: getattr(svc.stats, k) for k in SERVE_COUNTERS
            if hasattr(svc.stats, k)}


def cut(trace: dict, window: dict) -> dict:
    """What the readers read of a trace: the metadata, and the complete
    events that overlap ``window`` (the ``bench.window`` event) on its own
    thread or on a device's ops thread, with only the arguments read."""
    lo, hi = window["ts"], window["ts"] + window["dur"]
    ops = {(ev["pid"], ev["tid"]) for ev in trace["traceEvents"]
           if ev.get("ph") == "M" and ev.get("name") == "thread_name"
           and ev["args"]["name"] == trace_lib.OPS_THREAD}
    ops.add((window["pid"], window["tid"]))
    out = []
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "M":
            out.append(ev)
        elif ev.get("ph") == "X" and (ev["pid"], ev.get("tid")) in ops \
                and ev["ts"] < hi and ev["ts"] + ev.get("dur", 0.0) > lo:
            args = {k: v for k, v in ev.get("args", {}).items()
                    if k in KEEP_ARGS}
            out.append(dict({k: ev[k] for k in
                             ("ph", "pid", "tid", "ts", "dur", "name")
                             if k in ev}, args=args))
    return {"traceEvents": out}


def span_cost_ns(jax, n: int = 100_000) -> dict:
    """Host nanoseconds per span enter and exit, no profiler session."""
    out = {}
    for label, kw in (("plain", {}),
                      ("flush_args", dict(trigger="deadline", n_pad=128,
                                          cap=1024, occupied=1, lanes=1))):
        t0 = time.perf_counter()
        for _ in range(n):
            with jax.profiler.TraceAnnotation("serve.flush", **kw):
                pass
        out[label] = (time.perf_counter() - t0) / n * 1e9
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--untraced", type=int, default=0)
    ap.add_argument("--keep", default="")
    args = ap.parse_args(argv)
    bench = registry.load_benchmark(run.ROOT)
    wl = registry.workload(bench, args.workload)
    cfg = registry.config(bench, wl["config"], run.ROOT)
    traffic = registry.traffic(wl["traffic"], BENCH_DIR)
    jax = run.start_jax()
    try:
        harness.require_devices(jax, int(wl["chips"]))
    except harness.NoDevice as e:
        print(f"trace_split: {e}", file=sys.stderr)
        return 2
    cell = harness.Cell(workload=wl, config=cfg, traffic=traffic,
                        seed=args.seed,
                        family=registry.family(cfg["family"], BENCH_DIR))
    drv = registry.driver(traffic["driver"], BENCH_DIR).Driver(cell)
    drv.setup(args.seconds)
    print(json.dumps({"setup_s": time.monotonic() - T_START}), flush=True)
    span = jax.profiler.TraceAnnotation

    def window():
        before = _counters(drv)
        with span(trace_lib.WINDOW_SPAN):
            win = drv.window(args.seconds, span)
        after = _counters(drv)
        return win, {k: after[k] - before[k] for k in after}

    for i in range(args.untraced):
        win, served = window()
        print(json.dumps({"window": i, "traced": False, "e2e": win.e2e,
                          "counters": dict(win.counters, **served),
                          "queue_wait_ms": phases.queue_wait_ms(served)}),
              flush=True)

    trace_dir = os.path.join(run.ROOT, ".bench_trace")
    harness._traced(jax, trace_dir)
    try:
        win, served = window()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.trace.json.gz"))
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    summary = trace_lib.reduce_trace(raw)
    ph = phases.reduce_phases(raw)
    if args.keep:
        w = [ev for ev in raw["traceEvents"]
             if ev.get("name") == trace_lib.WINDOW_SPAN][0]
        with gzip.open(args.keep, "wt") as f:
            json.dump(cut(raw, w), f, separators=(",", ":"))

    flushes = len(ph.spans.get(phases.FLUSH, []))
    per = flushes or win.counters.get("solves") or 1
    print(json.dumps({
        "window": args.untraced, "traced": True, "e2e": win.e2e,
        "counters": dict(win.counters, **served),
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "op_s": ph.op_s, "scope_s": ph.scope_s,
        "scope_share": {k: phases.phase_share(ph, k) for k in ph.scope_s},
        "scoped_share": (100.0 * sum(ph.scope_s.values()) / ph.op_s
                         if ph.op_s else None),
        "flush_ms_p95": phases.flush_ms(ph),
        "flush_device_share": phases.flush_device_share(ph),
        "queue_wait_ms": phases.queue_wait_ms(served),
        "spans_per": "flush" if flushes else "solve",
        "split": phases.split(ph, per),
        "breakdown": summary.breakdown(top=16)}), flush=True)
    print(json.dumps({"span_cost_ns": span_cost_ns(jax)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

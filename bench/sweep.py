#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: offer its traffic
mix at each of several fixed rates, in one process, and print a line per
rate.

    python3 bench/sweep.py --workload tenants.poisson --seed 7 \\
        --seconds 8 --rates 100,200,400,800

A rate is sustained when the service sheds no request and the load
generator's 95th-percentile lag stays under the service's deadline
(``serve_max_wait_ms``).  The cell then offers a fixed rate, about four
fifths of the highest sustained one, written into its traffic file; the
benchmark's own runs never sweep.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from benchlib import harness, registry, stats

    bench = registry.load_benchmark(run.ROOT)
    wl = registry.workload(bench, args.workload)
    cfg = registry.config(bench, wl["config"], run.ROOT)
    traffic = registry.traffic(wl["traffic"], BENCH_DIR)
    jax = run.start_jax()
    try:
        harness.require_devices(jax, int(wl["chips"]))
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    driver = registry.driver(traffic["driver"], BENCH_DIR)
    family = registry.family(cfg["family"], BENCH_DIR)
    limit_ms = cfg["engine"]["params"].get("serve_max_wait_ms", 50.0)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = harness.Cell(workload=wl, config=cfg,
                            traffic=dict(traffic, rate_per_s=rate),
                            seed=args.seed, family=family)
        drv = driver.Driver(cell)
        drv.setup(args.seconds)
        win = drv.window(args.seconds, lambda _: contextlib.nullcontext())
        c = win.counters
        lag = stats.percentile(win.samples["generator_lag_ms"], 95)
        shed = c["requests"] - c["accepted"]
        print(json.dumps({
            "rate_per_s": rate, "requests": c["requests"], "shed": shed,
            "unresolved": c["accepted"] - c["resolved"],
            "latency_ms_p50": win.e2e["latency_ms_p50"],
            "latency_ms_p95": win.e2e["latency_ms_p95"],
            "generator_lag_ms_p95": lag,
            "flushes": {k: c[k] for k in driver.FLUSH_COUNTERS},
            "sustained": shed == 0 and lag < limit_ms}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run a cell with the control or a planted fault in the program's place,
on several seeds, in one process, and print what the check read.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 2 \\
        [--what sound|control|altered|unchanged|half_left_out]

``sound`` runs the program itself (the readings the limits' lower ends
come from); ``control`` puts the bfloat16 reference in its place; the
others are the faults of :mod:`benchlib.controls`.  Each run prints one
JSON line with its seed, ``correct`` and the checked numbers.  The
benchmark's own runs (``bench/run.py``) never use these.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--what", default="control")
    args = ap.parse_args(argv)
    from benchlib import controls, harness, registry

    bench = registry.load_benchmark(run.ROOT)
    wl = registry.workload(bench, args.workload)
    driver = registry.traffic(wl["traffic"], BENCH_DIR)["driver"]
    if args.what == "sound":
        hooks = {}
    elif args.what == "control":
        hooks = controls.control_hooks(driver)
    else:
        hooks = controls.fault_hooks(driver, args.what, BENCH_DIR)
    run.start_jax()
    t = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            res = harness.run_cell(bench, args.workload, seed, args.seconds,
                                   False, t_start=t, root=run.ROOT,
                                   bench_dir=BENCH_DIR, hooks=hooks)
        except harness.NoDevice as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"what": args.what, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "checks": res["checks"]}), flush=True)
        t = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration and a traffic mix; set-up builds and warms it, the
window measures for ``--seconds``, the outputs are compared with the
benchmark's own reference, and the last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, last, ``checks``: each number compared,
with its limit, which also end standard error.

The run refuses to start anywhere but on a TPU with the chips the cell
asks for, and exits non-zero without a result line when it cannot run.
JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, or in ``.jax_cache/`` at the root of
the checkout, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start_jax():
    """Put the program on the path and the compile cache in place; return
    the jax module."""
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "core", "mst_api.py")):
        sys.exit(f"bench: no program at {src}; run from a checkout")
    sys.path.insert(0, src)
    from repro import platform
    platform.use_compile_cache()
    import jax
    # Cache every program, however fast it compiled, so that set-up after
    # a checkout's first run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, BENCH_DIR)
    from benchlib import harness, registry

    bench = registry.load_benchmark(ROOT)
    registry.workload(bench, args.workload)
    start_jax()
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START, root=ROOT,
                                  bench_dir=BENCH_DIR)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

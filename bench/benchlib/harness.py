"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

A cell is a workload of ``BENCHMARK.json``: a configuration (a graph
family with its generator parameters, the engine settings and the chips)
under a traffic mix (a driver and its parameters).  The driver named by
the mix does the cell's own work in three calls -- ``setup(seconds)``,
``window(seconds, span)`` and ``check()`` -- and everything around them is
here: the clock for ``setup_s``, compile metering, the profiler, the
device and its memory, and choosing which metrics the cell reports.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import time
from typing import Optional

from benchlib import meter as meter_lib
from benchlib import peaks, registry
from benchlib import trace as trace_lib


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips a cell asks for."""


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    seed: int
    family: object                  # the configuration's family module
    mesh: object = None             # a 1-D mesh ("x") for multi-chip cells
    hooks: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    """What a driver's measured window gives back."""

    window_s: float                 # seconds the window's work took
    attempted: int                  # solves or requests in the window
    e2e: dict                       # end-to-end metric name -> value
    counters: dict = dataclasses.field(default_factory=dict)
    samples: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Record:
    """Everything a per-layer metric reader may read."""

    window: Window
    trace: Optional[trace_lib.TraceSummary]
    setup_compile_s: float


def require_devices(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devices[0].platform!r}; the "
                       "benchmark runs on the chip only")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    peaks.peaks_for(devices[0].device_kind)
    return devices


def memory_peak_bytes(devices) -> Optional[int]:
    peak = None
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return peak


def _traced(jax, trace_dir: str):
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, root: str = registry.ROOT,
             bench_dir: str = registry.BENCH_DIR, hooks: Optional[dict] = None,
             require_device: bool = True, log=sys.stderr) -> dict:
    """Run the cell once and return its result line as a dict.

    ``t_start`` is the process's start on the ``time.monotonic`` clock:
    set-up is everything from there to the window.  ``hooks`` replace the
    program under the driver (the control and the planted faults);
    ``require_device=False`` skips the look for a chip, for tests."""
    import jax

    wl = registry.workload(bench, workload)
    cfg = registry.config(bench, wl["config"], root)
    traffic = registry.traffic(wl["traffic"], bench_dir)
    chips = int(wl["chips"])
    if require_device:
        devices = require_devices(jax, chips)[:chips]
    else:
        devices = jax.devices()[:chips]
    meter = meter_lib.CompileMeter().install(jax)
    mesh = None
    if chips > 1:
        from repro import compat
        mesh = compat.make_mesh((chips,), ("x",))
    cell = Cell(workload=wl, config=cfg, traffic=traffic, seed=int(seed),
                family=registry.family(cfg["family"], bench_dir), mesh=mesh,
                hooks=dict(hooks or {}))
    driver = registry.driver(traffic["driver"], bench_dir).Driver(cell)

    driver.setup(seconds)
    setup_s = time.monotonic() - t_start
    setup_compile_s, setup_compiles = meter.mark()
    print(f"setup_s={setup_s} compiles={setup_compiles} "
          f"compile_s={setup_compile_s}", file=log, flush=True)

    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        _traced(jax, trace_dir)
    try:
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            win = driver.window(seconds, jax.profiler.TraceAnnotation)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = meter.count - setup_compiles
    summary = None
    if trace:
        summary = trace_lib.read_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak = memory_peak_bytes(devices)
    print(f"window_s={win.window_s} attempted={win.attempted} "
          f"window_compiles={window_compiles} counters={win.counters}",
          file=log, flush=True)

    checks, failed = driver.check()
    correct = all(value <= limit for value, limit in checks.values())

    e2e, layer = registry.cell_metrics(bench, workload)
    metrics = {}
    if not trace:
        values = dict(win.e2e, setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        rec = Record(window=win, trace=summary,
                     setup_compile_s=setup_compile_s)
        for m in layer:
            value = registry.metric_reader(m["name"], bench_dir)(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["window_compiles"] = window_compiles
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}={v} limit={lim}", file=log, flush=True)
    return result

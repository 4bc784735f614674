"""Open loop of small graphs offered to the MST service at a fixed rate.

Set-up draws every request of the window from the seed (the
configuration's family, ``count = rate * seconds``), builds the arrival
schedule from the traffic mix (:mod:`benchlib.load`), starts
``MSTService`` with the configuration's engine parameters and warms it
through its own ``submit``/``drain`` path: for every bucket shape the
window's requests reach, one flush at each width the service can
dispatch.

The window is one thread, as the service is driven in a deployment: it
waits for each scheduled arrival while calling ``poll`` (which flushes
full or expired buckets in this thread), submits the request backdated to
its scheduled time, and after the last arrival keeps polling until every
accepted request has resolved, or a minute past the window's close.  The
benchmark stamps each request itself, when its future resolves.

Latency runs from the scheduled arrival to the resolved forest, over all
requests: one that was shed, or never resolved, counts as failed and
takes the time from its arrival to the end of the wait.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from benchlib import harness, load, reference, stats

WAIT_AFTER_CLOSE_S = 60.0
_SLEEP_S = 1e-3
FLUSH_COUNTERS = ("size_flushes", "deadline_flushes", "drain_flushes")


class Driver:
    def __init__(self, cell: harness.Cell):
        self.cell = cell
        self.gen = cell.config["generator"]

    def setup(self, seconds: float) -> None:
        from repro.core import pipeline
        from repro.core.graph import Graph
        from repro.core.params import DEFAULT_PARAMS
        from repro.launch import serve

        cell = self.cell
        params = dataclasses.replace(
            DEFAULT_PARAMS, **cell.config["engine"].get("params", {}))
        self.offsets = load.arrival_offsets(cell.traffic, cell.seed, seconds)
        rng = load.rng(cell.seed, 2)
        self.requests = [cell.family.draw(self.gen, rng)
                         for _ in self.offsets]
        self.graphs = [Graph(n, s, d, w) for n, s, d, w in self.requests]
        self.service = cell.hooks.get("service", serve.MSTService)(params)
        self.shed_error = serve.ShedError

        buckets: dict = {}
        for req in self.requests:
            shape = pipeline.bucket_shape(
                req[0], len(req[1]), bucket=params.batch_bucket,
                max_vertices=params.batch_max_vertices or None,
                max_edges=params.batch_max_edges or None)
            buckets.setdefault(shape, []).append(req)
        widths = self.service.flush_widths()
        top = max(widths)
        for members in buckets.values():
            warm = (members * top)[:top]
            for width in widths:
                for n, s, d, w in warm[:width]:
                    self.service.submit(Graph(n, s, d, w))
                self.service.drain()

    def _stamp(self, i: int, _future) -> None:
        self.done_at[i] = time.monotonic()
        self.resolved += 1

    def window(self, seconds: float, span) -> harness.Window:
        svc, clock = self.service, time.monotonic
        n = len(self.graphs)
        self.done_at = [None] * n
        self.futures = [None] * n
        self.resolved = 0
        lag = np.zeros(n)
        before = {k: getattr(svc.stats, k, 0) for k in FLUSH_COUNTERS}
        t0 = clock()
        sched = t0 + self.offsets
        accepted = 0
        for i in range(n):
            t_arr = sched[i]
            while clock() < t_arr:
                with span("bench.poll"):
                    svc.poll()
                now = clock()
                if now < t_arr:
                    with span("bench.sleep"):
                        time.sleep(min(t_arr - now, _SLEEP_S))
            lag[i] = clock() - t_arr
            try:
                with span("bench.submit"):
                    fut = svc.submit(self.graphs[i], t_arrival=float(t_arr))
            except self.shed_error:
                continue
            accepted += 1
            self.futures[i] = fut
            fut.add_done_callback(functools.partial(self._stamp, i))
            with span("bench.poll"):
                svc.poll()
        close = t0 + seconds
        while self.resolved < accepted and clock() < close + \
                WAIT_AFTER_CLOSE_S:
            with span("bench.poll"):
                svc.poll()
            with span("bench.sleep"):
                time.sleep(_SLEEP_S)
        t_end = clock()

        latency_ms = [((self.done_at[i] if self.done_at[i] is not None
                        else t_end) - sched[i]) * 1e3 for i in range(n)]
        after = {k: getattr(svc.stats, k, 0) for k in FLUSH_COUNTERS}
        flushes = {k: after[k] - before[k] for k in FLUSH_COUNTERS}
        return harness.Window(
            window_s=t_end - t0, attempted=n,
            e2e={"latency_ms_p50": stats.percentile(latency_ms, 50),
                 "latency_ms_p95": stats.percentile(latency_ms, 95)},
            counters=dict(flushes, requests=n, accepted=accepted,
                          resolved=self.resolved),
            samples={"generator_lag_ms": list(lag * 1e3)})

    def check(self):
        wrong, unanswered, shed = 0, 0, 0
        for fut, (n, s, d, w) in zip(self.futures, self.requests):
            if fut is None:
                shed += 1
                continue
            if not fut.done():
                unanswered += 1
                continue
            ids = reference.msf_edge_ids(n, s, d, w)
            want = reference.edge_rows(s[ids], d[ids], w[ids])
            try:
                mask = np.asarray(fut.result().edge_mask, bool)
                got = reference.edge_rows(s[mask], d[mask], w[mask])
            except Exception:           # a raised answer is a wrong one
                wrong += 1
                continue
            wrong += reference.wrong_edges(got, want) > 0
        return ({"wrong_forests": (wrong, 0), "unanswered": (unanswered, 0)},
                shed + wrong + unanswered)

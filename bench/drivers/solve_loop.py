"""Closed loop of whole solves of one large graph.

Set-up has the program build the configuration's graph on the device
(its own generator and §3.1 preprocessing) and solves it once, which
compiles (or loads) every program a solve runs: the build, the interval
program and the compaction ladder.  The window then calls
``mst_api.minimum_spanning_forest`` back to back, each call ending with
the forest on the host, until ``seconds`` have passed; a window shorter
than one solve still measures one whole solve.

``edges_per_s`` is the canonical edge count times the solves, over the
seconds from the window's start to the end of its last solve.

The check compares every window solve's forest, as ``(u, v, weight)``
edges read through the program's own host view of its input, with the
reference forest (SciPy) of the graph as the benchmark's own copy of the
generator and of §3.1 preprocessing builds it on the host after the
window.  A build that drew or preprocessed the graph wrongly shows there
as forest edges that differ.
"""
from __future__ import annotations

import dataclasses
import time

from benchlib import harness, reference


class Driver:
    def __init__(self, cell: harness.Cell):
        self.cell = cell
        self.gen = cell.config["generator"]
        self.solver = cell.hooks.get("solver", program_solve)
        self.edges = None
        self.masks = []

    def setup(self, seconds: float) -> None:
        self.edges = self.cell.family.program_input(self.gen, self.cell.mesh)
        self.solver(self)

    def window(self, seconds: float, span) -> harness.Window:
        rounds, masks = 0, []
        t0 = time.perf_counter()
        while True:
            with span("bench.solve"):
                mask, r = self.solver(self)
            masks.append(mask)
            rounds += r
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.masks = masks
        m = self.edges.num_edges
        return harness.Window(
            window_s=elapsed, attempted=len(masks),
            e2e={"edges_per_s": m * len(masks) / elapsed},
            counters={"solves": len(masks), "rounds": rounds, "edges": m})

    def check(self):
        graph = self.edges.to_graph()
        self.edges = None               # free the program's device state
        n, src, dst, w = self.cell.family.reference_graph(self.gen)
        ids = reference.msf_edge_ids(n, src, dst, w)
        want = reference.edge_rows(src[ids], dst[ids], w[ids])
        worst, failed, seen = 0, 0, {}
        for mask in self.masks:
            if id(mask) not in seen:    # a solver may hand back one array
                got = reference.edge_rows(graph.src[mask], graph.dst[mask],
                                          graph.weight[mask])
                seen[id(mask)] = reference.wrong_edges(got, want)
            worst = max(worst, seen[id(mask)])
            failed += seen[id(mask)] > 0
        return {"wrong_edges": (worst, 0)}, failed


def program_solve(driver: Driver):
    """The timed path: one whole solve, forest on the host."""
    from repro.core import mst_api
    from repro.core.params import DEFAULT_PARAMS

    engine = driver.cell.config["engine"]
    params = dataclasses.replace(DEFAULT_PARAMS, **engine.get("params", {}))
    res, stats = mst_api.minimum_spanning_forest(
        driver.edges, method=engine["method"], params=params,
        mesh=driver.cell.mesh)
    return res.edge_mask, int(getattr(stats, "rounds", 0))

"""The control and the planted faults that the check has to catch.

The control puts the reference in the program's place, computed one
precision below what the configurations state (bfloat16 weights for
float32): the forests it gives break the stated guarantee, the exact
forest under (weight, canonical id), and the run must read ``correct``
false.  The faults break the program's timed path underneath an otherwise
normal run:

* ``altered``: one answer changed where it is produced (one edge of a
  forest flipped);
* ``unchanged``: a solve that returns its state as it started (no edge
  elected);
* ``half_left_out``: half of the served requests never answered.

Each is a ``hooks`` dict for :func:`benchlib.harness.run_cell`; the
benchmark's own runs use none of them.
"""
from __future__ import annotations

import types
from concurrent.futures import Future

import numpy as np

from benchlib import reference, registry

FAULTS = {
    "solve_loop": ("altered", "unchanged"),
    "open_loop": ("altered", "half_left_out"),
}


def _flip_one(mask) -> np.ndarray:
    mask = np.array(mask, bool)
    mask[int(np.flatnonzero(mask)[0]) if mask.any() else 0] ^= True
    return mask


def _control_solver(driver):
    """bfloat16 reference forest of the cell's graph, as a canonical mask."""
    if not hasattr(driver, "_control_mask"):
        n, src, dst, w = driver.cell.family.reference_graph(driver.gen)
        mask = np.zeros(len(src), bool)
        mask[reference.msf_edge_ids(n, src, dst, w, "bfloat16")] = True
        driver._control_mask = mask
    return driver._control_mask, 0


class ReferenceService:
    """The reference in ``MSTService``'s place: queued requests resolve at
    the next ``poll`` with their bfloat16 reference forests."""

    def __init__(self, params):
        self.stats = types.SimpleNamespace(size_flushes=0,
                                           deadline_flushes=0,
                                           drain_flushes=0)
        self._queue = []

    def flush_widths(self):
        return [1]

    def submit(self, graph, *, t_arrival=None):
        fut = Future()
        self._queue.append((graph, fut))
        return fut

    def poll(self, now=None):
        queue, self._queue = self._queue, []
        for g, fut in queue:
            mask = np.zeros(g.num_edges, bool)
            mask[reference.msf_edge_ids(g.num_vertices, g.src, g.dst,
                                        g.weight, "bfloat16")] = True
            fut.set_result(types.SimpleNamespace(edge_mask=mask))
        if queue:
            self.stats.deadline_flushes += 1
        return int(bool(queue))

    drain = poll


class _FaultyService:
    """Wraps the real service; ``transform(k, result)`` rewrites the answer
    to the k-th request of the window (a submit with a scheduled arrival,
    so warm-up requests are left alone), or withholds it by returning
    ``None``."""

    def __init__(self, inner, transform):
        self.inner = inner
        self.stats = inner.stats
        self.transform = transform
        self.count = 0

    def flush_widths(self):
        return self.inner.flush_widths()

    def submit(self, graph, *, t_arrival=None):
        inner = self.inner.submit(graph, t_arrival=t_arrival)
        if t_arrival is None:
            return inner
        out, k = Future(), self.count
        self.count += 1

        def relay(fut):
            res = self.transform(k, fut.result())
            if res is not None:
                out.set_result(res)

        inner.add_done_callback(relay)
        return out

    def poll(self, now=None):
        return self.inner.poll(now)

    def drain(self, now=None):
        return self.inner.drain(now)


def _altered(k, res):
    if k:
        return res
    return types.SimpleNamespace(edge_mask=_flip_one(res.edge_mask))


def _half_left_out(k, res):
    return None if k % 2 else res


def control_hooks(driver: str) -> dict:
    if driver == "solve_loop":
        return {"solver": _control_solver}
    if driver == "open_loop":
        return {"service": ReferenceService}
    raise KeyError(f"no control for driver {driver!r}")


def fault_hooks(driver: str, fault: str,
                bench_dir: str = registry.BENCH_DIR) -> dict:
    if fault not in FAULTS.get(driver, ()):
        raise KeyError(f"driver {driver!r} has no fault {fault!r}; "
                       f"options: {FAULTS.get(driver, ())}")
    if driver == "solve_loop":
        solve = registry.driver(driver, bench_dir).program_solve

        def solver(d):
            mask, rounds = solve(d)
            if fault == "altered":
                return _flip_one(mask), rounds
            return np.zeros(len(mask), bool), rounds

        return {"solver": solver}

    from repro.launch import serve
    transform = _altered if fault == "altered" else _half_left_out
    return {"service": lambda params: _FaultyService(
        serve.MSTService(params), transform)}

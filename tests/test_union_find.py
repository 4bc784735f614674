"""Pointer doubling to its fixpoint (``union_find.pointer_double``).

The loop stops once a step moves nothing, capped at ⌈log2 N⌉ steps; the
parent it returns must equal that of ⌈log2 N⌉ unconditional steps (the
loop it replaced, kept here as ``fixed_steps``) on every input and on
every engine path, and the steps it reports are what the engines count
in ``BoruvkaStats.doubling_steps``."""
import functools
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (boruvka_dist, generators, kruskal_ref, pipeline,
                        union_find)
from repro.core.graph import preprocess
from repro.core.params import GHSParams

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _cap(n):
    return max(1, math.ceil(math.log2(max(n, 2))))


@functools.partial(jax.jit, static_argnames=("num_steps",))
def fixed_steps(parent, num_steps=None):
    """The doubling this module replaced: ``num_steps`` (default ⌈log2 N⌉)
    gathers, whatever the forest's depth."""
    num_steps = _cap(parent.shape[0]) if num_steps is None else num_steps
    p = jax.lax.fori_loop(0, num_steps, lambda _, p: p[p], parent)
    return p, jnp.int32(num_steps)


def _depth(parent):
    """Longest parent path to a root of a forest (numpy)."""
    parent = np.asarray(parent)
    cur = np.arange(parent.size)
    hops = np.zeros(parent.size, np.int64)
    while True:
        nxt = parent[cur]
        moved = nxt != cur
        if not moved.any():
            return int(hops.max())
        hops += moved
        cur = nxt


def _expected_steps(parent, num_steps=None):
    """⌈log2 depth⌉ steps flatten a forest, one more confirms it."""
    cap = _cap(len(parent)) if num_steps is None else num_steps
    d = _depth(parent)
    return min(cap, (math.ceil(math.log2(d)) if d > 1 else 0) + 1)


def _hook_forest(n, seed):
    """A forest as the engines build it: ``hook_min`` over random merge
    requests with ``hi > lo``."""
    rng = np.random.default_rng(seed)
    k = 3 * n
    a = rng.integers(0, n, k)
    b = rng.integers(0, n, k)
    valid = a != b
    hi = np.maximum(a, b).astype(np.uint32)
    lo = np.minimum(a, b).astype(np.uint32)
    return np.asarray(union_find.hook_min(
        n, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(valid)))


def _chain(n):
    return np.concatenate([[0], np.arange(n - 1)]).astype(np.uint32)


def _star(n):
    return np.zeros(n, np.uint32)


CASES = {
    "identity": (np.arange(37, dtype=np.uint32), None),
    "star": (_star(50), None),
    "hook_min_0": (_hook_forest(64, 0), None),
    "hook_min_1": (_hook_forest(1000, 1), None),
    "hook_min_2": (_hook_forest(4096, 2), None),
    "chain_full_cap": (_chain(64), None),          # depth 63 needs all 6
    "chain_confirm": (_chain(33), None),           # depth 32: 5 + confirm
    "capped_below_depth": (_chain(100), 3),
    "hook_min_capped": (_hook_forest(1000, 3), 1),
}


@pytest.mark.parametrize("case", list(CASES) + ["vmap_lanes"])
def test_pointer_double_matches_fixed_steps(case):
    if case == "vmap_lanes":
        # Lanes of different depths: the batched loop runs until the
        # deepest lane stops, and each lane reports its own count.
        n = 64
        lanes = np.stack([np.arange(n, dtype=np.uint32), _star(n),
                          _chain(n), _chain(9).tolist() + list(range(9, n)),
                          _hook_forest(n, 4)]).astype(np.uint32)
        got, steps = jax.vmap(union_find.pointer_double)(jnp.asarray(lanes))
        want, _ = jax.vmap(fixed_steps)(jnp.asarray(lanes))
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert np.asarray(steps).tolist() == [
            _expected_steps(lane) for lane in lanes]
        return
    parent, num_steps = CASES[case]
    got, steps = union_find.pointer_double(jnp.asarray(parent), num_steps)
    want, _ = fixed_steps(jnp.asarray(parent), num_steps)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(steps) == _expected_steps(parent, num_steps)
    if num_steps is None:
        assert int(steps) <= _cap(len(parent))
        flat = np.asarray(got)
        assert np.array_equal(flat[flat], flat)


# -- the engines' count ---------------------------------------------------

def _path_graph(n):
    """A path whose weights rise along it: every vertex elects its edge
    to the left, so one round hooks a chain of depth ``n - 1``."""
    src = np.arange(n - 1, dtype=np.int32)
    w = (np.arange(1, n, dtype=np.float32) / np.float32(2 * n))
    return preprocess(src, src + 1, w, n)


def _star_graph(n):
    """Vertex 0 joined to every other: one round hooks a star."""
    leaves = np.arange(1, n, dtype=np.int32)
    w = (leaves.astype(np.float32) / np.float32(2 * n))
    return preprocess(np.zeros(n - 1, np.int32), leaves, w, n)


@pytest.mark.parametrize("graph,depth", [
    (_path_graph(9), 8), (_path_graph(33), 32), (_star_graph(40), 1)],
    ids=["chain9", "chain33", "star40"])
def test_doubling_steps_counts_fixpoint_and_confirm(graph, depth):
    """One round merges everything into a forest of depth ``depth``:
    ⌈log2 depth⌉ steps flatten it and one confirms it; the second round
    finds no edge and confirms its identity parent in one step.  The
    count rides the interval's readback: no host sync is added."""
    res, st = boruvka_dist.minimum_spanning_forest(graph)
    want = kruskal_ref.kruskal(graph)
    assert np.array_equal(res.edge_mask, want.edge_mask)
    assert st.rounds == 2
    first = (math.ceil(math.log2(depth)) if depth > 1 else 0) + 1
    assert st.doubling_steps == first + 1
    assert st.host_syncs == st.intervals + 1


def _clear_engine_caches():
    boruvka_dist._build_interval_fn.cache_clear()
    boruvka_dist._build_interval_fn_fused.cache_clear()
    boruvka_dist._build_batch_interval_fn.cache_clear()


@pytest.fixture
def both_doublings(monkeypatch):
    """``run()`` once with :func:`fixed_steps` in place of the fixpoint
    loop and once as it is, each with the engines' interval programs
    traced anew: returns ``(fixed, fixpoint)``."""
    fixpoint = union_find.pointer_double

    def solve(run):
        out = []
        for fn in (fixed_steps, fixpoint):
            _clear_engine_caches()
            monkeypatch.setattr(union_find, "pointer_double", fn)
            out.append(run())
        return out

    yield solve
    monkeypatch.undo()
    _clear_engine_caches()


def _grid2d(scale, seed):
    return pipeline.build_host(pipeline.GraphSpec("grid2d", scale, seed=seed))


@pytest.mark.parametrize("graph", ["rmat9", "grid2d8", "chain33"])
@pytest.mark.parametrize("round_kernel", ["xla", "pallas"])
def test_single_graph_forests_match_fixed_doubling(graph, round_kernel,
                                                   both_doublings):
    g = {"rmat9": lambda: generators.generate("rmat", 9, seed=3),
         "grid2d8": lambda: _grid2d(8, 1),
         "chain33": lambda: _path_graph(33)}[graph]()
    params = GHSParams(round_kernel=round_kernel)
    (r_old, s_old), (r_new, s_new) = both_doublings(
        lambda: boruvka_dist.minimum_spanning_forest(g, params))
    assert np.array_equal(r_old.edge_mask, r_new.edge_mask)
    assert s_old.rounds == s_new.rounds
    assert s_new.host_syncs == s_new.intervals + 1
    assert 0 < s_new.doubling_steps <= s_old.doubling_steps


def test_batched_forests_match_fixed_doubling(both_doublings):
    """Packed buckets of graphs of different depths, one lane's count
    per round (the slowest)."""
    graphs = [generators.generate("rmat", 6, seed=s) for s in range(3)]
    graphs += [_path_graph(33), _star_graph(20), _grid2d(6, 2)]
    (old, s_old), (new, s_new) = both_doublings(
        lambda: boruvka_dist.minimum_spanning_forests(graphs))
    for a, b in zip(old, new):
        assert np.array_equal(a.edge_mask, b.edge_mask)
    assert s_old.rounds_per_graph == s_new.rounds_per_graph
    assert s_new.host_syncs == s_new.intervals + s_new.extra_syncs
    assert 0 < s_new.doubling_steps < s_old.doubling_steps


def test_sharded_forests_match_fixed_doubling():
    """Four CPU devices, both collectives: the replicated parent gives
    every shard the same exit, and the forests and round counts equal
    those of fixed-step doubling."""
    code = """
import functools, json, math
import jax, jax.numpy as jnp, numpy as np
from repro.compat import make_mesh
from repro.core import boruvka_dist, generators, pipeline, union_find
from repro.core.params import GHSParams

@functools.partial(jax.jit, static_argnames=("num_steps",))
def fixed_steps(parent, num_steps=None):
    n = parent.shape[0]
    num_steps = (max(1, math.ceil(math.log2(max(n, 2))))
                 if num_steps is None else num_steps)
    p = jax.lax.fori_loop(0, num_steps, lambda _, p: p[p], parent)
    return p, jnp.int32(num_steps)

def clear():
    boruvka_dist._build_interval_fn.cache_clear()
    boruvka_dist._build_interval_fn_fused.cache_clear()

mesh = make_mesh((4,), ("x",))
fixpoint = union_find.pointer_double
rows = []
for g in (generators.generate("rmat", 8, seed=5),
          pipeline.build_host(pipeline.GraphSpec("grid2d", 8, seed=5))):
    for coll in ("pmin", "compressed"):
        p = GHSParams(collective=coll, check_frequency=2)
        out = {}
        for name, fn in (("fixed", fixed_steps), ("fixpoint", fixpoint)):
            clear()
            union_find.pointer_double = fn
            out[name] = boruvka_dist.minimum_spanning_forest(g, p, mesh=mesh)
        (r0, s0), (r1, s1) = out["fixed"], out["fixpoint"]
        rows.append(dict(
            same=bool(np.array_equal(r0.edge_mask, r1.edge_mask)),
            rounds=bool(s0.rounds == s1.rounds),
            steps=bool(0 < s1.doubling_steps < s0.doubling_steps),
            sync=bool(s1.host_syncs == s1.intervals + 1)))
print(json.dumps(rows))
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(rows) == 4
    assert all(all(r.values()) for r in rows), rows

"""Incremental MST (DESIGN.md §13): bit-identity of apply_updates vs a
from-scratch re-solve, the cycle/cut probe's certificates, the update
stats ledger, and shard-count invariance.

Randomized-batch budget (the acceptance floor is 200 batches over ≥ 3
scenario kinds × 1/2/4 shards):
  * in-process streams:  3 kinds × 4 seeds × 12 chained batches = 144
  * subprocess shard sweep: 3 shard counts × 3 kinds × 8 batches =  72
                                                              -----
                                                               216
Every batch is checked bit-identical against BOTH the Kruskal oracle and
a plain Borůvka re-solve of the merged graph (the definition of the
updated graph — `apply_edge_batch`)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import generators, kruskal_ref, runtime
from repro.core.graph import PAD_VERTEX, preprocess
from repro.core.incremental import (EdgeBatch, IncrementalForest,
                                    _apply_edge_batch_reference,
                                    apply_edge_batch, apply_updates,
                                    finalize_plan, plan_updates)
from repro.core.mst_api import (incremental_forest, minimum_spanning_forest,
                                minimum_spanning_forests)
from repro.core import mst_api
from repro.core.params import GHSParams
from repro.kernels.spmv_minplus import ops as minplus_ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

STREAM_KINDS = ("rmat", "grid", "chain")
STREAM_SEEDS = (0, 1, 2, 3)
STREAM_BATCHES = 12


def run_child(code: str, devices: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def _assert_identical(got, want, g, ctx):
    assert np.array_equal(got.edge_mask, want.edge_mask), ctx
    assert np.array_equal(
        np.sort(g.weight[got.edge_mask].view(np.uint32)),
        np.sort(g.weight[want.edge_mask].view(np.uint32))), ctx
    assert got.num_components == want.num_components, ctx
    assert got.num_tree_edges == want.num_tree_edges, ctx


def _solve(graph, params=None) -> IncrementalForest:
    state, _ = incremental_forest(
        graph, params=params or GHSParams())
    return state


def _check_update(state, batch, params=None, ctx=None):
    """apply_updates == Kruskal == plain Borůvka on the merged graph."""
    params = params or GHSParams()
    new_state, st = apply_updates(state, batch, params=params)
    g2 = apply_edge_batch(state.graph, batch)
    want = kruskal_ref.kruskal(g2)
    plain, _ = minimum_spanning_forest(g2, method="boruvka")
    _assert_identical(new_state.forest, want, g2, ctx)
    _assert_identical(new_state.forest, plain, g2, ctx)
    # stats protocol: the probe's fused readback + the sub-solve's syncs
    assert st.host_syncs == st.intervals + st.extra_syncs, ctx
    assert 0 <= st.candidate_count <= g2.num_edges, ctx
    return new_state, st


def _random_batch(rng, state, n_ins=6, n_tree_del=2, n_rand_del=2):
    """Inserts + tree-edge deletes + arbitrary-pair deletes."""
    g = state.graph
    n = g.num_vertices
    ins = [(int(rng.integers(0, n)), int(rng.integers(0, n)),
            float(rng.random() * 0.98 + 0.01)) for _ in range(n_ins)]
    dels = []
    tree = np.flatnonzero(state.forest.edge_mask)
    if tree.size and n_tree_del:
        for i in rng.choice(tree, size=min(n_tree_del, tree.size),
                            replace=False):
            dels.append((int(g.src[i]), int(g.dst[i])))
    dels += [(int(rng.integers(0, n)), int(rng.integers(0, n)))
             for _ in range(n_rand_del)]
    return EdgeBatch.make(ins, dels)


# ---------------------------------------------------------------------------
# EdgeBatch contract
# ---------------------------------------------------------------------------

def test_edge_batch_make_and_counts():
    b = EdgeBatch.make([(0, 1, 0.5), (2, 3, 0.25)], [(4, 5)])
    assert (b.num_inserts, b.num_deletes, b.size) == (2, 1, 3)
    assert b.insert_weight.dtype == np.float32
    empty = EdgeBatch.make()
    assert empty.size == 0


def test_edge_batch_validation():
    with pytest.raises(ValueError, match="endpoints"):
        EdgeBatch.make([(0, 99, 0.5)]).validate(16)
    with pytest.raises(ValueError, match="endpoints"):
        EdgeBatch.make([], [(-1, 3)]).validate(16)
    with pytest.raises(ValueError, match=r"\(0, 1\)"):
        EdgeBatch.make([(0, 1, 1.5)]).validate(16)
    with pytest.raises(ValueError, match=r"\(0, 1\)"):
        EdgeBatch.make([(0, 1, 0.0)]).validate(16)
    EdgeBatch.make([(0, 15, 0.5)]).validate(16)   # in-range is fine


def test_empty_batch_is_identity():
    state = _solve(generators.generate("rmat", 6, seed=1))
    new_state, st = _check_update(state, EdgeBatch.make(), ctx="empty")
    assert st.updates_applied == 0
    assert np.array_equal(new_state.forest.edge_mask,
                          state.forest.edge_mask)
    # the merged graph IS the old graph (canonical form is a fixpoint)
    assert np.array_equal(new_state.graph.src, state.graph.src)
    assert np.array_equal(new_state.graph.weight.view(np.uint32),
                          state.graph.weight.view(np.uint32))


# ---------------------------------------------------------------------------
# Adversarial single-batch cases
# ---------------------------------------------------------------------------

def test_self_loop_insert_is_noop():
    state = _solve(generators.generate("rmat", 6, seed=2))
    _, st = _check_update(
        state, EdgeBatch.make([(3, 3, 0.5), (7, 7, 0.01)]), ctx="loops")
    assert st.updates_applied == 0


def test_duplicate_inserts_keep_min_weight():
    """The same pair inserted twice in one batch: §3.1 preprocess keeps the
    minimum copy, and the probe sees only the canonical edge."""
    state = _solve(generators.generate("rmat", 6, seed=3))
    g = state.graph
    # a pair not present in the old graph
    u, v = 0, 1
    pid = set(zip(g.src.tolist(), g.dst.tolist()))
    while (u, v) in pid or (v, u) in pid or u == v:
        v += 1
    batch = EdgeBatch.make([(u, v, 0.7), (v, u, 0.2), (u, v, 0.9)])
    new_state, st = _check_update(state, batch, ctx="dup-insert")
    i = np.flatnonzero((new_state.graph.src == u)
                       & (new_state.graph.dst == v))
    assert i.size == 1
    assert new_state.graph.weight[i[0]] == np.float32(0.2)
    assert st.updates_applied == 1        # ONE structural change


def test_parallel_insert_of_existing_edge():
    """Inserting a heavier copy of an existing pair is structurally a
    no-op (the survivor wins); a lighter copy re-weights the pair and
    voids its old certificate."""
    state = _solve(generators.generate("rmat", 6, seed=4))
    g = state.graph
    i = int(np.flatnonzero(state.forest.edge_mask)[0])
    u, v, w = int(g.src[i]), int(g.dst[i]), float(g.weight[i])
    # heavier copy: nothing changes
    _, st = _check_update(
        state, EdgeBatch.make([(u, v, min(w + 0.01, 0.99))]), ctx="heavier")
    assert st.updates_applied == 0
    # lighter copy: one re-weight, and the (still lightest-path) edge stays
    new_state, st = _check_update(
        state, EdgeBatch.make([(u, v, w / 2)]), ctx="lighter")
    assert st.updates_applied == 1


def test_insert_existing_forest_edge_same_weight_is_noop():
    state = _solve(generators.generate("rmat", 6, seed=5))
    g = state.graph
    i = int(np.flatnonzero(state.forest.edge_mask)[3])
    batch = EdgeBatch.make([(int(g.src[i]), int(g.dst[i]),
                             float(g.weight[i]))])
    new_state, st = _check_update(state, batch, ctx="reinsert-tree")
    assert st.updates_applied == 0
    assert np.array_equal(new_state.forest.edge_mask,
                          state.forest.edge_mask)


def test_delete_non_tree_edge_keeps_forest():
    state = _solve(generators.generate("rmat", 6, seed=6))
    g = state.graph
    non_tree = np.flatnonzero(~state.forest.edge_mask)
    i = int(non_tree[0])
    batch = EdgeBatch.make([], [(int(g.src[i]), int(g.dst[i]))])
    new_state, st = _check_update(state, batch, ctx="del-non-tree")
    assert st.updates_applied == 1
    # same tree weights (canonical ids shifted, so compare the multiset)
    assert np.array_equal(
        np.sort(g.weight[state.forest.edge_mask].view(np.uint32)),
        np.sort(new_state.graph.weight[
            new_state.forest.edge_mask].view(np.uint32)))
    assert new_state.forest.num_components == state.forest.num_components


def test_delete_absent_pair_is_noop():
    state = _solve(generators.generate("rmat", 6, seed=7))
    g = state.graph
    pid = set(zip(g.src.tolist(), g.dst.tolist()))
    u, v = 0, 1
    while (u, v) in pid or u == v:
        v += 1
    _, st = _check_update(
        state, EdgeBatch.make([], [(u, v), (5, 5)]), ctx="del-absent")
    assert st.updates_applied == 0


def test_delete_bridge_without_replacement_splits_forest():
    """chain: every edge is a bridge with NO replacement — the severed
    component stays severed and the component count grows."""
    state = _solve(generators.generate("chain", 5, seed=0))
    g = state.graph
    i = int(np.flatnonzero(state.forest.edge_mask)[4])
    batch = EdgeBatch.make([], [(int(g.src[i]), int(g.dst[i]))])
    new_state, st = _check_update(state, batch, ctx="bridge")
    assert new_state.forest.num_components \
        == state.forest.num_components + 1
    assert st.replacement_probes == 0     # nothing crosses the cut


def test_delete_tree_edge_with_replacement_probes_the_cut():
    """A deleted tree edge whose cut has crossing non-tree edges: the cut
    probe counts them and the final solve elects the lightest."""
    state = _solve(generators.generate("rmat", 6, seed=8))
    g = state.graph
    tree = np.flatnonzero(state.forest.edge_mask)
    # find a tree edge with at least one replacement: delete and check
    for i in tree[:8]:
        batch = EdgeBatch.make([], [(int(g.src[i]), int(g.dst[i]))])
        new_state, st = _check_update(state, batch, ctx=("cut", int(i)))
        if new_state.forest.num_components \
                == state.forest.num_components:
            assert st.replacement_probes > 0
            return
    pytest.fail("no replaceable tree edge found in the first 8")


def test_delete_and_reinsert_same_pair_in_one_batch():
    """ISSUE contract: a pair both deleted and inserted is deleted from
    the OLD graph first, then re-inserted (possibly re-weighted)."""
    state = _solve(generators.generate("rmat", 6, seed=9))
    g = state.graph
    i = int(np.flatnonzero(state.forest.edge_mask)[0])
    u, v = int(g.src[i]), int(g.dst[i])
    batch = EdgeBatch.make([(u, v, 0.995)], [(u, v)])
    new_state, st = _check_update(state, batch, ctx="del+ins")
    j = np.flatnonzero((new_state.graph.src == u)
                       & (new_state.graph.dst == v))
    assert j.size == 1
    assert new_state.graph.weight[j[0]] == np.float32(0.995)


def test_update_from_empty_graph_builds_forest():
    """No anchor forest exists — the keep-all path solves from scratch."""
    g0 = preprocess(np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.float32), 8)
    state = _solve(g0)
    assert state.forest.num_components == 8
    batch = EdgeBatch.make([(i, i + 1, 0.1 * (i + 1)) for i in range(7)])
    new_state, st = _check_update(state, batch, ctx="from-empty")
    assert new_state.forest.num_components == 1
    assert st.updates_applied == 7


def test_delete_every_edge_empties_the_graph():
    state = _solve(generators.generate("chain", 4, seed=1))
    g = state.graph
    batch = EdgeBatch.make(
        [], [(int(u), int(v)) for u, v in zip(g.src, g.dst)])
    new_state, st = _check_update(state, batch, ctx="delete-all")
    assert new_state.graph.num_edges == 0
    assert new_state.forest.num_components == g.num_vertices


def test_sorted_merge_matches_preprocess_reference():
    """apply_edge_batch's sorted-merge fast path must be bit-identical to
    the preprocess-based definition across deletes, colliding inserts
    (lighter, heavier, AND exact-tie copies), duplicate inserts,
    self-loops, and empty graphs."""
    rng = np.random.default_rng(11)
    for trial in range(30):
        n = int(rng.integers(2, 64))
        m = int(rng.integers(0, 150))
        g = preprocess(rng.integers(0, n, m), rng.integers(0, n, m),
                       rng.random(m, dtype=np.float32) * 0.98 + 0.01, n)
        ins = [(int(rng.integers(0, n)), int(rng.integers(0, n)),
                float(rng.random() * 0.98 + 0.01))
               for _ in range(int(rng.integers(0, 10)))]
        if g.num_edges:                       # exact-tie + heavier + lighter
            i = int(rng.integers(0, g.num_edges))
            w = float(g.weight[i])
            ins += [(int(g.src[i]), int(g.dst[i]), w),
                    (int(g.dst[i]), int(g.src[i]), min(w * 1.5, 0.99)),
                    (int(g.src[i]), int(g.dst[i]), w / 2)]
        if ins:                               # duplicate insert pair
            ins.append(ins[0])
        dels = [(int(rng.integers(0, n)), int(rng.integers(0, n)))
                for _ in range(int(rng.integers(0, 5)))]
        if g.num_edges:
            j = int(rng.integers(0, g.num_edges))
            dels.append((int(g.dst[j]), int(g.src[j])))
        batch = EdgeBatch.make(ins, dels)
        got = apply_edge_batch(g, batch)
        want = _apply_edge_batch_reference(g, batch)
        assert got.num_edges == want.num_edges, trial
        assert np.array_equal(got.src, want.src), trial
        assert np.array_equal(got.dst, want.dst), trial
        assert np.array_equal(got.weight.view(np.uint32),
                              want.weight.view(np.uint32)), trial


def test_adversarial_corpus_updates_exact():
    from test_mst_correctness import _adversarial_corpus
    rng = np.random.default_rng(0)
    for name, g in _adversarial_corpus():
        state = _solve(g)
        _check_update(state, _random_batch(rng, state), ctx=name)


# ---------------------------------------------------------------------------
# Stats ledger
# ---------------------------------------------------------------------------

def test_updates_applied_counts_structural_changes_exactly():
    state = _solve(generators.generate("rmat", 6, seed=10))
    g = state.graph
    tree = np.flatnonzero(state.forest.edge_mask)
    i, j = int(tree[0]), int(tree[1])
    pid = set(zip(g.src.tolist(), g.dst.tolist()))
    u, v = 0, 1
    while (u, v) in pid or u == v:
        v += 1
    batch = EdgeBatch.make(
        inserts=[(u, v, 0.5),                                # added
                 (int(g.src[j]), int(g.dst[j]),
                  float(g.weight[j]) / 2),                   # re-weighted
                 (3, 3, 0.5)],                               # loop: no-op
        deletes=[(int(g.src[i]), int(g.dst[i]))])            # removed
    _, st = _check_update(state, batch, ctx="ledger")
    assert st.updates_applied == 3
    assert st.filter_passes == 1
    assert st.edges_filtered \
        == apply_edge_batch(g, batch).num_edges - st.candidate_count


def test_probe_shrinks_the_final_solve():
    """The point of the pass: on a mostly-unchanged graph the certificates
    drop a large share of edges before the final solve."""
    state = _solve(generators.generate("rmat", 8, seed=0))
    rng = np.random.default_rng(1)
    _, st = _check_update(state, _random_batch(rng, state),
                          params=GHSParams(update_levels=32), ctx="shrink")
    assert st.candidate_count < state.graph.num_edges // 2
    assert st.edges_filtered > 0


def test_plan_finalize_split_matches_apply_updates():
    """The serving layer's path — plan, solve the candidates separately
    (batched), finalize — is bit-identical to the one-call façade."""
    state = _solve(generators.generate("rmat", 6, seed=11))
    rng = np.random.default_rng(2)
    batch = _random_batch(rng, state)
    plan = plan_updates(state, batch)
    forests, _ = minimum_spanning_forests([plan.sub])
    via_plan = finalize_plan(plan, forests[0])
    direct, _ = apply_updates(state, batch)
    assert np.array_equal(via_plan.forest.edge_mask,
                          direct.forest.edge_mask)
    assert via_plan.forest.num_components == direct.forest.num_components


# ---------------------------------------------------------------------------
# Param surfaces: both engines' knobs flow through the final solve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,params", [
    ("default", GHSParams()),
    ("pallas-round", GHSParams(round_kernel="pallas")),
    ("pallas-segmin", GHSParams(use_pallas=True)),
    ("host-loop", GHSParams(round_loop="host")),
    ("no-compaction", GHSParams(compaction="none")),
    ("hashed", GHSParams(partitioner="hashed")),
    ("levels-1", GHSParams(update_levels=1)),
    ("levels-64", GHSParams(update_levels=64)),
])
def test_param_surface_identical(name, params):
    g = generators.generate("rmat", 6, seed=12)
    state, _ = incremental_forest(g, params=params)
    rng = np.random.default_rng(3)
    _check_update(state, _random_batch(rng, state), params=params,
                  ctx=name)


def test_handle_from_any_engine_is_equivalent():
    """Forests are bit-identical across engines, so a handle solved with
    GHS or filter-Borůvka updates identically to the Borůvka one."""
    g = generators.generate("rmat", 6, seed=13)
    rng = np.random.default_rng(4)
    batch = _random_batch(rng, state=_solve(g))
    masks = {}
    for method in ("boruvka", "ghs", "filter_boruvka"):
        state, _ = incremental_forest(g, method=method)
        new_state, _ = mst_api.apply_updates(state, batch)
        masks[method] = new_state.forest.edge_mask
    assert np.array_equal(masks["boruvka"], masks["ghs"])
    assert np.array_equal(masks["boruvka"], masks["filter_boruvka"])


def test_update_levels_sweep_identical():
    """The level count quantizes the cycle certificate — it may change the
    candidate count, never the forest."""
    state = _solve(generators.generate("rmat", 7, seed=14))
    rng = np.random.default_rng(5)
    batch = _random_batch(rng, state)
    masks = []
    for levels in (1, 4, 16, 64):
        new_state, _ = apply_updates(
            state, batch, params=GHSParams(update_levels=levels))
        masks.append(new_state.forest.edge_mask)
    for m in masks[1:]:
        assert np.array_equal(m, masks[0])


# ---------------------------------------------------------------------------
# component_maxkey vs a union-find oracle
# ---------------------------------------------------------------------------

def _oracle_maxkey(n, src, dst, key, active):
    dsu = kruskal_ref._DSU(n)
    for u, v, a in zip(src, dst, active):
        if a:
            dsu.union(int(u), int(v))
    root = np.asarray([dsu.find(v) for v in range(n)])
    mx = np.zeros(n, dtype=np.uint64)
    for u, k, a in zip(src, key, active):
        if a:
            r = root[int(u)]
            mx[r] = max(mx[r], np.uint64(k))
    return mx[root]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_component_maxkey_matches_union_find(seed):
    import jax
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 100))
    m = int(rng.integers(0, 300))
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    key = rng.integers(1, 2**63, size=m, dtype=np.uint64)
    active = rng.random(m) < 0.5
    with jax.enable_x64():
        comp, mk = minplus_ops.component_maxkey(
            src, dst, np.asarray(key), active, num_vertices=n)
    want = _oracle_maxkey(n, src, dst, key, active)
    assert np.array_equal(np.asarray(mk), want), seed
    # warm-start from the converged labels: bit-identical result
    with jax.enable_x64():
        comp2, mk2 = minplus_ops.component_maxkey(
            src, dst, np.asarray(key), active, num_vertices=n,
            init=comp)
    assert np.array_equal(np.asarray(comp2), np.asarray(comp))
    assert np.array_equal(np.asarray(mk2), np.asarray(mk))


def test_component_maxkey_padding_inert():
    """PAD_VERTEX lanes with active=False never reach the scatter-max."""
    import jax
    src = np.asarray([0, 2, PAD_VERTEX], np.int32)
    dst = np.asarray([1, 3, PAD_VERTEX], np.int32)
    key = np.asarray([7, 9, 2**63 - 1], np.uint64)
    active = np.asarray([True, True, False])
    with jax.enable_x64():
        comp, mk = minplus_ops.component_maxkey(
            src, dst, key, active, num_vertices=5)
    assert np.array_equal(np.asarray(comp), [0, 0, 2, 2, 4])
    assert np.array_equal(np.asarray(mk), [7, 7, 9, 9, 0])


# ---------------------------------------------------------------------------
# Randomized interleaved streams (3 kinds × 4 seeds × 12 chained batches)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", STREAM_KINDS)
@pytest.mark.parametrize("seed", STREAM_SEEDS)
def test_randomized_update_stream(kind, seed):
    rng = np.random.default_rng(1000 + seed)
    state = _solve(generators.generate(kind, 6, seed=seed))
    for step in range(STREAM_BATCHES):
        batch = _random_batch(
            rng, state,
            n_ins=int(rng.integers(0, 8)),
            n_tree_del=int(rng.integers(0, 3)),
            n_rand_del=int(rng.integers(0, 3)))
        state, _ = _check_update(state, batch, ctx=(kind, seed, step))


# ---------------------------------------------------------------------------
# Hypothesis property test
# ---------------------------------------------------------------------------

def test_incremental_property_randomized():
    pytest.importorskip(
        "hypothesis",
        reason="optional dev dependency (see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st_

    @st_.composite
    def cases(draw):
        n = draw(st_.integers(min_value=2, max_value=40))
        m = draw(st_.integers(min_value=0, max_value=120))
        seed = draw(st_.integers(min_value=0, max_value=2**31 - 1))
        n_ins = draw(st_.integers(min_value=0, max_value=10))
        n_tdel = draw(st_.integers(min_value=0, max_value=4))
        levels = draw(st_.integers(min_value=1, max_value=16))
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        w = rng.random(m, dtype=np.float32) * 0.98 + 0.01
        return preprocess(src, dst, w, n), seed, n_ins, n_tdel, levels

    @settings(max_examples=20, deadline=None)
    @given(cases())
    def inner(case):
        g, seed, n_ins, n_tdel, levels = case
        state = _solve(g)
        rng = np.random.default_rng(seed ^ 0x5EED)
        batch = _random_batch(rng, state, n_ins=n_ins,
                              n_tree_del=n_tdel, n_rand_del=2)
        _check_update(state, batch,
                      params=GHSParams(update_levels=levels),
                      ctx=(seed, n_ins, n_tdel, levels))

    inner()


# ---------------------------------------------------------------------------
# Shard sweep (subprocess: device count locks at jax init)
# 3 shard counts × 3 kinds × 8 chained batches = 72 randomized batches
# ---------------------------------------------------------------------------

def test_apply_updates_1_2_4_shards_identical():
    out = run_child("""
import numpy as np, json
from repro.compat import make_mesh
from repro.core import generators, kruskal_ref
from repro.core.incremental import EdgeBatch, apply_edge_batch, apply_updates
from repro.core.mst_api import incremental_forest, minimum_spanning_forest
from repro.core.params import GHSParams

def random_batch(rng, state):
    g = state.graph
    n = g.num_vertices
    ins = [(int(rng.integers(0, n)), int(rng.integers(0, n)),
            float(rng.random() * 0.98 + 0.01))
           for _ in range(int(rng.integers(0, 7)))]
    dels = []
    tree = np.flatnonzero(state.forest.edge_mask)
    if tree.size:
        for i in rng.choice(tree, size=min(2, tree.size), replace=False):
            dels.append((int(g.src[i]), int(g.dst[i])))
    return EdgeBatch.make(ins, dels)

params = GHSParams(update_levels=4, partitioner="hashed")
rows = []
for shards in (1, 2, 4):
    mesh = make_mesh((shards,), ("x",)) if shards > 1 else None
    for ki, kind in enumerate(("rmat", "grid", "chain")):
        rng = np.random.default_rng(shards * 100 + ki)
        g = generators.generate(kind, 6, seed=7)
        state, _ = incremental_forest(g, params=params, mesh=mesh)
        ok = True
        for step in range(8):
            batch = random_batch(rng, state)
            state, st = apply_updates(state, batch, params=params,
                                      mesh=mesh)
            want = kruskal_ref.kruskal(
                state.graph)  # state.graph IS the merged graph
            ok = ok and bool(np.array_equal(
                state.forest.edge_mask, want.edge_mask))
            ok = ok and st.host_syncs == st.intervals + st.extra_syncs
        # one sharded from-scratch re-solve of the final graph
        plain, _ = minimum_spanning_forest(state.graph, mesh=mesh,
                                           params=params)
        ok = ok and bool(np.array_equal(
            state.forest.edge_mask, plain.edge_mask))
        rows.append(dict(shards=shards, kind=kind, ok=ok))
print(json.dumps(rows))
""", devices=4)
    rows = json.loads(out.strip().splitlines()[-1])
    assert len(rows) == 9
    bad = [r for r in rows if not r["ok"]]
    assert not bad, bad

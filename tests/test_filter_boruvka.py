"""Filter-Borůvka hybrid (DESIGN.md §10): bit-identity, sampler contract,
connectivity probe, empty-sample guarantee, shard-count invariance."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import generators, kruskal_ref, pipeline
from repro.core.filter_boruvka import MAX_PASSES
from repro.core.graph import PAD_VERTEX, Graph, preprocess
from repro.core.mst_api import minimum_spanning_forest
from repro.core.params import GHSParams
from repro.kernels.spmv_minplus import ops as minplus_ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


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
    # weight multiset equality (bit-exact, via the raw float32 patterns)
    assert np.array_equal(
        np.sort(g.weight[got.edge_mask].view(np.uint32)),
        np.sort(g.weight[want.edge_mask].view(np.uint32))), ctx
    assert got.num_components == want.num_components, ctx
    assert got.num_tree_edges == want.num_tree_edges, ctx


# ---------------------------------------------------------------------------
# Bit-identity: oracle + plain engine, generated + adversarial graphs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["rmat", "random", "disconnected"])
@pytest.mark.parametrize("rate", [0.0, 0.1, 0.5, 1.0])
def test_filter_matches_kruskal_and_boruvka(kind, rate):
    g = generators.generate(kind, 8, seed=11)
    want = kruskal_ref.kruskal(g)
    plain, _ = minimum_spanning_forest(g, method="boruvka")
    got, st = minimum_spanning_forest(
        g, method="filter_boruvka",
        params=GHSParams(filter_sample_rate=rate))
    _assert_identical(got, want, g, (kind, rate))
    _assert_identical(got, plain, g, (kind, rate))
    assert 1 <= st.filter_passes <= MAX_PASSES
    assert st.edges_filtered == g.num_edges - st.survivor_history[-1]


def test_adversarial_corpus_filter_exact():
    from test_mst_correctness import _adversarial_corpus
    for name, g in _adversarial_corpus():
        want = kruskal_ref.kruskal(g)
        for rate in (0.0, 0.4, 1.0):
            got, _ = minimum_spanning_forest(
                g, method="filter_boruvka",
                params=GHSParams(filter_sample_rate=rate))
            _assert_identical(got, want, g, (name, rate))


def test_filter_levels_sweep_identical():
    """The level count quantizes the cycle rule — it may only change how
    many edges are dropped, never the forest."""
    g = generators.generate("rmat", 9, seed=4)
    want = kruskal_ref.kruskal(g)
    filtered = []
    for levels in (1, 2, 16, 64):
        got, st = minimum_spanning_forest(
            g, method="filter_boruvka",
            params=GHSParams(filter_sample_rate=0.25,
                             filter_levels=levels))
        _assert_identical(got, want, g, levels)
        filtered.append(st.edges_filtered)
    # more levels → a sharper path-max bound → monotone non-decreasing drops
    assert filtered == sorted(filtered)


def test_filter_knob_validation():
    g = generators.generate("rmat", 6, seed=0)
    with pytest.raises(ValueError, match="filter_levels"):
        minimum_spanning_forest(g, method="filter_boruvka",
                                params=GHSParams(filter_levels=0))


def test_filter_recursion_bound():
    """A tiny threshold forces the recursion; it still runs at most
    MAX_PASSES sample→solve→filter passes and stays exact."""
    g = generators.generate("random", 8, seed=2)
    want = kruskal_ref.kruskal(g)
    got, st = minimum_spanning_forest(
        g, method="filter_boruvka",
        params=GHSParams(filter_sample_rate=0.2, filter_threshold=1))
    _assert_identical(got, want, g, "recursion")
    assert st.filter_passes == MAX_PASSES
    assert len(st.survivor_history) == MAX_PASSES


# ---------------------------------------------------------------------------
# Empty-sample guarantee (satellite: p=0 regression)
# ---------------------------------------------------------------------------

def test_empty_sample_keeps_isolated_vertex_bridge():
    """With p=0 the Bernoulli sample is empty: the sampler must never have
    dropped anything — the final solve sees the FULL edge set, including
    the single bridge that connects an otherwise-isolated vertex."""
    rng = np.random.default_rng(7)
    n = 40
    src = rng.integers(0, n - 1, 300)
    dst = rng.integers(0, n - 1, 300)
    w = rng.random(300, dtype=np.float32) * 0.9 + 0.05
    # vertex n-1 hangs off the graph by exactly one (heavy) edge
    src = np.concatenate([src, [0]])
    dst = np.concatenate([dst, [n - 1]])
    w = np.concatenate([w, np.float32([0.99])])
    g = preprocess(src, dst, w, n)
    bridge = np.flatnonzero((g.src == 0) & (g.dst == n - 1))
    assert bridge.size == 1

    want = kruskal_ref.kruskal(g)
    got, st = minimum_spanning_forest(
        g, method="filter_boruvka",
        params=GHSParams(filter_sample_rate=0.0))
    _assert_identical(got, want, g, "p=0")
    assert got.edge_mask[bridge[0]]          # the bridge is in the forest
    assert st.edges_filtered == 0            # nothing was dropped...
    assert st.survivor_history == (g.num_edges,)  # ...full survivor set
    assert st.filter_passes == 1


# ---------------------------------------------------------------------------
# Sampler contract
# ---------------------------------------------------------------------------

def test_sampler_numpy_jnp_identical_and_slice_invariant():
    import jax.numpy as jnp
    import jax
    eid = np.arange(5000, dtype=np.uint64)
    m_np = np.asarray(pipeline.sample_mask(3, 0.37, eid))
    with jax.enable_x64():
        m_j = np.asarray(pipeline.sample_mask(3, 0.37, jnp.asarray(eid)))
    assert np.array_equal(m_np, m_j)
    # per-edge decisions do not depend on which shard holds the edge:
    # any slicing of the id space reproduces the same bits
    parts = [pipeline.sample_mask(3, 0.37, eid[i::4]) for i in range(4)]
    rebuilt = np.empty_like(m_np)
    for i, p in enumerate(parts):
        rebuilt[i::4] = p
    assert np.array_equal(rebuilt, m_np)
    # endpoints are exact
    assert not pipeline.sample_mask(3, 0.0, eid).any()
    assert pipeline.sample_mask(3, 1.0, eid).all()
    # distinct seeds give distinct streams
    assert not np.array_equal(m_np, pipeline.sample_mask(4, 0.37, eid))
    # rate is honored within a loose tolerance
    assert abs(m_np.mean() - 0.37) < 0.05


def test_sampler_fixed_k_exact_size():
    import jax.numpy as jnp
    import jax
    eid = np.arange(700, dtype=np.uint64)
    m_np = pipeline.sample_mask_fixed_k(np, 5, 123, eid)
    with jax.enable_x64():
        m_j = np.asarray(
            pipeline.sample_mask_fixed_k(jnp, 5, 123, jnp.asarray(eid)))
    assert np.array_equal(m_np, m_j)
    assert m_np.sum() == 123
    assert not pipeline.sample_mask_fixed_k(np, 5, 0, eid).any()
    assert pipeline.sample_mask_fixed_k(np, 5, 700, eid).all()


def test_sample_device_edges_matches_numpy():
    de = pipeline.build(pipeline.GraphSpec(kind="rmat", scale=7, seed=5),
                        None)
    got = np.asarray(pipeline.sample_device_edges(de, 0.3, seed=9))
    want = pipeline.sample_mask(
        9, 0.3, np.arange(de.num_edges, dtype=np.uint64))
    assert np.array_equal(got[:de.num_edges], want)
    assert not got[de.num_edges:].any()      # padding is never sampled


# ---------------------------------------------------------------------------
# Connectivity probe vs union-find oracle
# ---------------------------------------------------------------------------

def _oracle_labels(n, src, dst, active):
    dsu = kruskal_ref._DSU(n)
    for u, v, a in zip(src, dst, active):
        if a:
            dsu.union(int(u), int(v))
    return np.asarray([dsu.find(v) for v in range(n)])


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_connected_labels_matches_union_find(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 120))
    m = int(rng.integers(0, 400))
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    active = rng.random(m) < 0.6
    got = np.asarray(minplus_ops.connected_labels(
        src, dst, active, num_vertices=n))
    want = _oracle_labels(n, src, dst, active)
    # canonical labeling: every vertex labeled by its component's min id
    # (implies the partitions are equal)
    canon = np.empty(n, dtype=np.int64)
    for r in np.unique(want):
        members = np.flatnonzero(want == r)
        canon[members] = members.min()
    assert np.array_equal(got, canon)


def test_connected_labels_padding_inert():
    """PAD_VERTEX lanes with active=False must not perturb the labels."""
    src = np.asarray([0, 2, PAD_VERTEX, PAD_VERTEX], np.int32)
    dst = np.asarray([1, 3, PAD_VERTEX, PAD_VERTEX], np.int32)
    active = np.asarray([True, True, False, False])
    got = np.asarray(minplus_ops.connected_labels(
        src, dst, active, num_vertices=5))
    assert np.array_equal(got, [0, 0, 2, 2, 4])


def test_connected_labels_vmappable():
    """Batched probes (the per-level label build) share one compiled loop."""
    import jax
    src = np.asarray([0, 1, 2, 3], np.int32)
    dst = np.asarray([1, 2, 3, 4], np.int32)
    masks = np.asarray([[True, True, False, False],
                        [True, True, True, True],
                        [False, False, False, False]])
    got = np.asarray(jax.vmap(
        lambda a: minplus_ops.connected_labels(src, dst, a, num_vertices=5)
    )(masks))
    assert np.array_equal(got[0], [0, 0, 0, 3, 4])
    assert np.array_equal(got[1], [0, 0, 0, 0, 0])
    assert np.array_equal(got[2], np.arange(5))


# ---------------------------------------------------------------------------
# Shard sweep (subprocess: device count locks at jax init)
# ---------------------------------------------------------------------------

def test_filter_boruvka_1_2_4_shards_identical():
    out = run_child("""
import numpy as np, json
from repro.compat import make_mesh
from repro.core import generators, kruskal_ref
from repro.core.mst_api import minimum_spanning_forest
from repro.core.params import GHSParams
g = generators.generate("rmat", 9, seed=3)
want = kruskal_ref.kruskal(g)
filtered = set()
for shards in (1, 2, 4):
    mesh = make_mesh((shards,), ("x",)) if shards > 1 else None
    got, st = minimum_spanning_forest(
        g, method="filter_boruvka", mesh=mesh,
        params=GHSParams(filter_sample_rate=0.3, partitioner="hashed"))
    assert np.array_equal(got.edge_mask, want.edge_mask), shards
    filtered.add(st.edges_filtered)
# the filter decision set is shard-count invariant, not just the forest
assert len(filtered) == 1, filtered
print(json.dumps(dict(ok=True)))
""", devices=4)
    assert json.loads(out.strip().splitlines()[-1])["ok"]


# ---------------------------------------------------------------------------
# Property test (hypothesis): randomized graphs AND sample rates
# ---------------------------------------------------------------------------

def test_filter_property_randomized():
    pytest.importorskip(
        "hypothesis",
        reason="optional dev dependency (see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st_

    @st_.composite
    def cases(draw):
        n = draw(st_.integers(min_value=2, max_value=48))
        m = draw(st_.integers(min_value=0, max_value=160))
        seed = draw(st_.integers(min_value=0, max_value=2**31 - 1))
        rate = draw(st_.floats(min_value=0.0, max_value=1.0))
        levels = draw(st_.integers(min_value=1, max_value=20))
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        w = rng.random(m, dtype=np.float32) * 0.98 + 0.01
        return preprocess(src, dst, w, n), rate, levels

    @settings(max_examples=25, deadline=None)
    @given(cases())
    def inner(case):
        g, rate, levels = case
        want = kruskal_ref.kruskal(g)
        plain, _ = minimum_spanning_forest(g, method="boruvka")
        got, st = minimum_spanning_forest(
            g, method="filter_boruvka",
            params=GHSParams(filter_sample_rate=rate,
                             filter_levels=levels))
        _assert_identical(got, want, g, (rate, levels))
        _assert_identical(got, plain, g, (rate, levels))
        assert 1 <= st.filter_passes <= MAX_PASSES

    inner()

"""Device graph pipeline (DESIGN.md §7): byte-identity vs the numpy oracle,
partitioner forest-identity, determinism, and the packed-key cache."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import generators, kruskal_ref, pipeline
from repro.core.graph import Graph, pad_edges, pair_ids, preprocess
from repro.core.mst_api import minimum_spanning_forest
from repro.core.params import GHSParams
from repro.core.partition import PARTITIONERS, build_edge_layout, \
    get_partitioner
from repro.core.pipeline import GraphSpec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _graphs_equal(a: Graph, b: Graph) -> bool:
    return (a.num_vertices == b.num_vertices
            and np.array_equal(a.src, b.src)
            and np.array_equal(a.dst, b.dst)
            and np.array_equal(a.weight.view(np.uint32),
                               b.weight.view(np.uint32)))


# ---------------------------------------------------------------------------
# Byte-identity: device pipeline vs numpy oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", pipeline.KINDS)
@pytest.mark.parametrize("scale", [7, 9])
def test_device_build_byte_identical_to_host(kind, scale):
    spec = GraphSpec(kind, scale, seed=3)
    host = pipeline.build_host(spec)
    dev = pipeline.build(spec)
    assert dev.num_edges == host.num_edges
    assert _graphs_equal(host, dev.to_graph())
    host.validate()


def test_device_build_sharded_byte_identical():
    """1/2/4-shard device builds all reproduce the numpy oracle exactly
    (sample i never depends on its shard, and the dedup sort is global)."""
    out = _run_child(r"""
import json
import numpy as np
from repro.compat import make_mesh
from repro.core import pipeline
from repro.core.pipeline import GraphSpec

rows = []
for shards in (1, 2, 4):
    mesh = make_mesh((shards,), ("x",)) if shards > 1 else None
    for kind in pipeline.KINDS:
        spec = GraphSpec(kind, 8, seed=5)
        h = pipeline.build_host(spec)
        d = pipeline.build(spec, mesh=mesh).to_graph()
        rows.append(dict(shards=shards, kind=kind, ok=bool(
            np.array_equal(h.src, d.src) and np.array_equal(h.dst, d.dst)
            and np.array_equal(h.weight.view(np.uint32),
                               d.weight.view(np.uint32)))))
print(json.dumps(rows))
""", devices=4)
    rows = json.loads(out.strip().splitlines()[-1])
    assert len(rows) == 3 * len(pipeline.KINDS)
    bad = [r for r in rows if not r["ok"]]
    assert not bad, bad


def test_device_edges_feed_engines():
    """DeviceEdges hand straight to both engines; forests match Kruskal on
    the byte-identical host mirror and the sync contract holds."""
    spec = GraphSpec("geo_knn", 9, seed=1)
    dev = pipeline.build(spec)
    want = kruskal_ref.kruskal(pipeline.build_host(spec))
    got_b, st = minimum_spanning_forest(dev, method="boruvka")
    assert np.array_equal(got_b.edge_mask, want.edge_mask)
    assert st.host_syncs == st.intervals + st.extra_syncs
    assert st.extra_syncs == 1               # the final state fetch
    got_g, _ = minimum_spanning_forest(dev, method="ghs")
    assert np.array_equal(got_g.edge_mask, want.edge_mask)


def test_prepare_edges_staging_signal():
    """prepare_edges records which path staged the input and WARNS when a
    DeviceEdges source silently misses the no-host-round-trip fast path
    (regression: the fallback used to be invisible)."""
    import warnings

    from repro.core import runtime

    spec = GraphSpec("rmat", 7, seed=2)
    dev = pipeline.build(spec)

    # Block layout, single shard: capacity % 1 == 0, fast path engages.
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # any warning here is a bug
        bundle = runtime.prepare_edges(dev, "block", None, chunk=8)
    assert bundle.staging == "device"

    # Non-block partitioner: host mirror, loudly.
    with pytest.warns(UserWarning, match="fast path"):
        bundle = runtime.prepare_edges(dev, "hashed", None, chunk=8)
    assert bundle.staging == "host"

    # Host Graph input: host staging is the contract, not a fallback.
    g = generators.generate("rmat", 6, seed=1)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        bundle = runtime.prepare_edges(g, "block", None, chunk=8)
    assert bundle.staging == "host"

    # The engine surfaces the taken path on its stats ledger.
    _, st = minimum_spanning_forest(dev, method="boruvka")
    assert st.edge_staging == "device"
    _, st = minimum_spanning_forest(g, method="boruvka")
    assert st.edge_staging == "host"


def test_prepare_edges_fast_path_every_shard_count():
    """The DeviceEdges fast path must engage for block layouts at every
    shard count the suite sweeps (pipeline capacities are pow2 multiples
    of the shard count, so capacity % num_shards == 0 by construction)."""
    out = _run_child(r"""
import json
import warnings
import numpy as np
from repro.compat import make_mesh
from repro.core import kruskal_ref, pipeline
from repro.core.mst_api import minimum_spanning_forest
from repro.core.pipeline import GraphSpec

rows = []
for shards in (1, 2, 4):
    mesh = make_mesh((shards,), ("x",)) if shards > 1 else None
    spec = GraphSpec("rmat", 8, seed=5)
    dev = pipeline.build(spec, mesh=mesh)
    want = kruskal_ref.kruskal(pipeline.build_host(spec))
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # a fast-path miss warns -> fails
        res, st = minimum_spanning_forest(dev, method="boruvka", mesh=mesh)
    rows.append(dict(shards=shards, staging=st.edge_staging,
                     exact=bool(np.array_equal(res.edge_mask,
                                               want.edge_mask))))
print(json.dumps(rows))
""", devices=4)
    rows = json.loads(out.strip().splitlines()[-1])
    assert [r["shards"] for r in rows] == [1, 2, 4]
    for r in rows:
        assert r["staging"] == "device", r
        assert r["exact"], r


# ---------------------------------------------------------------------------
# Partitioners
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("part", sorted(PARTITIONERS))
def test_partitioners_bit_identical_both_engines(part):
    g = generators.generate("rmat", 8, seed=11)
    want = kruskal_ref.kruskal(g)
    for loop in ("device", "host"):
        got, _ = minimum_spanning_forest(
            g, method="boruvka",
            params=GHSParams(partitioner=part, round_loop=loop))
        assert np.array_equal(got.edge_mask, want.edge_mask), (part, loop)
    gg = generators.generate("rmat", 7, seed=3)
    wg = kruskal_ref.kruskal(gg)
    got, _ = minimum_spanning_forest(
        gg, method="ghs", params=GHSParams(partitioner=part))
    assert np.array_equal(got.edge_mask, wg.edge_mask), part


def test_partitioner_star_hub_balanced_vs_block():
    """The adversarial star keeps every edge on vertex 0; all partitioners
    must still elect the exact Kruskal forest."""
    spec = GraphSpec("star", 8, seed=0)
    g = pipeline.build_host(spec)
    want = kruskal_ref.kruskal(g)
    for part in sorted(PARTITIONERS):
        got, _ = minimum_spanning_forest(
            g, method="boruvka", params=GHSParams(partitioner=part))
        assert np.array_equal(got.edge_mask, want.edge_mask), part


def test_edge_layout_covers_every_edge_once():
    g = generators.generate("random", 8, seed=2)
    for name in sorted(PARTITIONERS):
        layout = build_edge_layout(g, get_partitioner(name), 4, chunk=32)
        eids = layout.eid[layout.eid >= 0]
        assert np.array_equal(np.sort(eids), np.arange(g.num_edges))
        assert layout.num_slots == 4 * layout.block


@pytest.mark.parametrize("n,shards", [(10, 4), (130, 4), (7, 3), (64, 4)])
def test_vertex_perm_is_block_capacity_respecting_permutation(n, shards):
    """Regression: every partitioner's vertex relabeling must be a true
    permutation of [0, n) whose blocks respect the engine's block rule —
    including when the shard count does not divide n (the last block is
    short; the balanced snake must not leak ids ≥ n)."""
    rng = np.random.default_rng(0)
    g = preprocess(rng.integers(0, n, 6 * n), rng.integers(0, n, 6 * n),
                   rng.random(6 * n, dtype=np.float32) * 0.9 + 0.05, n)
    block = -(-n // shards)
    for name in sorted(PARTITIONERS):
        perm = get_partitioner(name).vertex_perm(g, shards)
        assert np.array_equal(np.sort(perm), np.arange(n)), name
        counts = np.bincount(perm // block, minlength=shards)
        assert counts.max() <= block, name


def test_ghs_balanced_partitioner_non_pow2_vertices():
    """End-to-end regression for the same bug class: GHS + balanced
    partitioning on a graph whose vertex count no shard count divides."""
    rng = np.random.default_rng(7)
    n, m = 130, 700
    g = preprocess(rng.integers(0, n, m), rng.integers(0, n, m),
                   rng.random(m, dtype=np.float32) * 0.9 + 0.05, n)
    want = kruskal_ref.kruskal(g)
    for part in sorted(PARTITIONERS):
        got, _ = minimum_spanning_forest(
            g, method="ghs", params=GHSParams(partitioner=part))
        assert np.array_equal(got.edge_mask, want.edge_mask), part


def test_preprocess_general_path_matches_oracle():
    """The scale > 17 device-preprocess branch (pair-id sort + segmented
    scatter-min; the narrow-key fast path cannot pack those scales) must
    match graph.preprocess bit-for-bit.  The branch is selected by the
    ``scale`` argument alone, so it is exercised directly on small arrays."""
    import jax
    import jax.numpy as jnp
    from repro.core.pipeline import _preprocess_device

    rng = np.random.default_rng(3)
    cap, m, n = 512, 400, 1 << 18
    src = rng.integers(0, n, cap).astype(np.uint64)
    dst = rng.integers(0, n, cap).astype(np.uint64)
    dst[::7] = src[::7]                     # self-loops
    dst[1::5] = dst[::5][:len(dst[1::5])]   # extra collisions
    src[1::5] = src[::5][:len(src[1::5])]
    w = (rng.integers(0, 1 << 23, cap).astype(np.float32) + 0.5) * 2.0 ** -23
    with jax.enable_x64():
        s, d, k, cnt = jax.jit(
            lambda s, d, w, c: _preprocess_device(
                s, d, w, c, num_samples=m, cap=cap, scale=18)
        )(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
          jnp.arange(cap, dtype=np.uint64))
    cnt = int(cnt)
    want = preprocess(src[:m], dst[:m], w[:m], n)
    assert cnt == want.num_edges
    assert np.array_equal(np.asarray(s)[:cnt], want.src)
    assert np.array_equal(np.asarray(d)[:cnt], want.dst)
    assert np.array_equal(np.asarray(k)[:cnt], want.packed_keys)


def test_unknown_partitioner_raises():
    g = generators.generate("random", 6, seed=2)
    with pytest.raises(ValueError, match="unknown partitioner"):
        minimum_spanning_forest(
            g, method="boruvka", params=GHSParams(partitioner="nope"))


# ---------------------------------------------------------------------------
# Determinism + satellite invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(generators.GENERATORS))
def test_generator_determinism(kind):
    """Same kind/scale/seed ⇒ identical graphs, run to run."""
    a = generators.generate(kind, 7, seed=13)
    b = generators.generate(kind, 7, seed=13)
    assert _graphs_equal(a, b)
    c = generators.generate(kind, 7, seed=14)
    assert not _graphs_equal(a, c)          # the seed actually matters


@pytest.mark.parametrize("kind", pipeline.KINDS)
def test_device_pipeline_determinism(kind):
    spec = GraphSpec(kind, 7, seed=13)
    assert _graphs_equal(pipeline.build(spec).to_graph(),
                         pipeline.build(spec).to_graph())


@pytest.mark.parametrize("scale", [7, 8, 9])
def test_grid2d_is_the_4_neighbour_lattice(scale):
    """GRID2D: every canonical edge joins lattice neighbours, no vertex has
    more than 4, the count is the lattice's, the weights are 23-bit draws
    that tie, and the device build's forest is Kruskal's."""
    spec = GraphSpec("grid2d", scale, seed=11)
    side = 1 << (scale // 2)
    rows = spec.num_vertices // side
    g = pipeline.build_host(spec)
    assert g.num_edges == rows * (side - 1) + (rows - 1) * side
    deg = np.bincount(np.concatenate([g.src, g.dst]),
                      minlength=spec.num_vertices)
    assert deg.max() == 4 and deg.min() == 2
    step = g.dst.astype(np.int64) - g.src.astype(np.int64)
    right = (step == 1) & (g.src % side != side - 1)
    assert (right | (step == side)).all()
    bits = g.weight.astype(np.float64) * 2.0 ** 23 - 0.5
    assert (bits == np.floor(bits)).all() and (bits < 2 ** 23).all()
    wide = pipeline.build_host(GraphSpec("grid2d", scale + 6, seed=11))
    assert np.unique(wide.weight).size < wide.num_edges
    want = kruskal_ref.kruskal(g)
    got, _ = minimum_spanning_forest(pipeline.build(spec))
    assert np.array_equal(got.edge_mask, want.edge_mask)
    assert int(got.edge_mask.sum()) == spec.num_vertices - 1


def test_packed_keys_cached_across_pads():
    g = generators.generate("random", 7, seed=5)
    first = g.packed_keys
    pad_edges(g, 64)
    pad_edges(g, 128)
    assert g.packed_keys is first          # one array, reused every pad


def test_pair_ids_checks_packing_precondition():
    u = np.array([1], dtype=np.int64)
    with pytest.raises(AssertionError, match="32-bit"):
        pair_ids(u, u + 1, 2 ** 32 + 1)


def test_preprocess_keeps_min_weight_copy():
    """Duplicate (u, v) samples collapse to the min-weight copy (§3.1)."""
    src = np.array([3, 1, 1, 3, 5, 1])
    dst = np.array([1, 3, 3, 1, 5, 3])     # (1,3) ×4 both directions; 5-loop
    w = np.array([0.5, 0.25, 0.75, 0.125, 0.9, 0.25], np.float32)
    g = preprocess(src, dst, w, 8)
    assert g.num_edges == 1
    assert (int(g.src[0]), int(g.dst[0])) == (1, 3)
    assert float(g.weight[0]) == 0.125


def _run_child(code: str, devices: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout

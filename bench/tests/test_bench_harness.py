"""The harness's own parts at toy sizes, on the CPU: generators, the
reference, the load generator, the statistics, and finding cells by name.
"""
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import harness, load, peaks, reference, registry, stats  # noqa: E402

RMAT = registry.family("rmat")
KNN = registry.family("superpixel_knn")
GEN = {"scale": 9, "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
       "graph_seed": 0}
KNN_GEN = {"min_nodes": 85, "max_nodes": 150, "k": 8}


# -- generators -------------------------------------------------------------

def test_rmat_is_deterministic_per_seed():
    """The graph is a function of the configuration's ``graph_seed``."""
    a = RMAT.reference_graph(GEN)
    b = RMAT.reference_graph(dict(GEN))
    c = RMAT.reference_graph(dict(GEN, graph_seed=2**31 + 8))
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("graph_seed", [0, 2**31 + 5])
def test_rmat_reference_graph_is_the_programs_graph(graph_seed):
    """The benchmark's copy of the sampler and of §3.1 gives the graph the
    program's own host path (``build_host``) gives."""
    from repro.core import pipeline
    gen = dict(GEN, graph_seed=graph_seed)
    n, src, dst, w = RMAT.reference_graph(gen)
    g = pipeline.build_host(pipeline.GraphSpec("rmat", 9, avg_degree=32,
                                               seed=graph_seed))
    assert n == g.num_vertices and (src < dst).all()
    np.testing.assert_array_equal(src, g.src)
    np.testing.assert_array_equal(dst, g.dst)
    np.testing.assert_array_equal(w.view(np.uint32), g.weight.view(np.uint32))


def test_rmat_device_build_matches_host_graph():
    n, src, dst, w = RMAT.reference_graph(GEN)
    edges = RMAT.program_input(GEN)
    assert edges.num_edges == len(src) and edges.capacity == 8192
    g = edges.to_graph()
    np.testing.assert_array_equal(g.src, src)
    np.testing.assert_array_equal(g.dst, dst)
    np.testing.assert_array_equal(g.weight.view(np.uint32), w.view(np.uint32))


def test_rmat_weights_keep_their_ties():
    """Uniform 23-bit weights collide, so the (weight, canonical id)
    tie-break is exercised."""
    _, _, _, w = RMAT.reference_graph(dict(GEN, scale=12))
    assert len(np.unique(w)) < len(w)
    assert ((w > 0) & (w < 1)).all()


def test_superpixel_graphs_are_deterministic_and_canonical():
    r1, r2 = load.rng(17, 2), load.rng(17, 2)
    for _ in range(20):
        (n, s, d, w), (n2, s2, d2, w2) = KNN.draw(KNN_GEN, r1), \
            KNN.draw(KNN_GEN, r2)
        assert n == n2 and 85 <= n <= 150
        np.testing.assert_array_equal(s, s2)
        np.testing.assert_array_equal(w, w2)
        assert (s < d).all() and (np.diff(s.astype(np.int64) * n + d) > 0).all()
        assert 4 * n <= len(s) <= 8 * n and ((w > 0) & (w < 1)).all()
    other = KNN.draw(KNN_GEN, load.rng(18, 2))
    assert not np.array_equal(KNN.draw(KNN_GEN, load.rng(17, 2))[3],
                              other[3])


# -- reference --------------------------------------------------------------

def _kruskal_rows(n, src, dst, w):
    from repro.core.graph import Graph
    from repro.core.kruskal_ref import kruskal
    mask = kruskal(Graph(n, src.astype(np.int32), dst.astype(np.int32),
                         w)).edge_mask
    return reference.edge_rows(src[mask], dst[mask], w[mask])


def _ties(seed):
    """A dense graph whose weights collide often: the tie-break decides."""
    r = np.random.default_rng(seed)
    n = 40
    u, v = np.triu_indices(n, 1)
    keep = r.random(len(u)) < 0.3
    w = ((r.integers(0, 8, keep.sum()) + 0.5) / 8).astype(np.float32)
    return n, u[keep], v[keep], w


@pytest.mark.parametrize("make", [
    lambda: RMAT.reference_graph(GEN),
    lambda: RMAT.reference_graph(dict(GEN, scale=7, graph_seed=2**31 + 3)),
    lambda: RMAT.reference_graph(dict(GEN, scale=12)),
    lambda: KNN.draw(KNN_GEN, load.rng(4, 2)),
    lambda: _ties(0),
    lambda: _ties(1),
])
def test_reference_agrees_with_kruskal(make):
    n, src, dst, w = make()
    ids = reference.msf_edge_ids(n, src, dst, w)
    got = reference.edge_rows(src[ids], dst[ids], w[ids])
    assert reference.wrong_edges(got, _kruskal_rows(n, src, dst, w)) == 0


def test_control_precision_breaks_the_forest():
    n, src, dst, w = RMAT.reference_graph(dict(GEN, scale=12))
    exact = reference.msf_edge_ids(n, src, dst, w)
    low = reference.msf_edge_ids(n, src, dst, w, "bfloat16")
    assert len(exact) == len(low)           # still a spanning forest
    assert reference.wrong_edges(
        reference.edge_rows(src[low], dst[low], w[low]),
        reference.edge_rows(src[exact], dst[exact], w[exact])) > 0


def test_bfloat16_rounding():
    w = np.array([1.0, 1.00390625, 1.005859375, 0.1], np.float32)
    got = reference.to_bfloat16(w)
    np.testing.assert_array_equal(got[:3], [1.0, 1.0, 1.0078125])
    assert abs(got[3] - 0.1) < 0.1 * 2 ** -8


# -- statistics and load ----------------------------------------------------

def test_percentile_is_nearest_rank_over_all():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile(xs + [1e9] * 6, 95) == 1e9


def test_poisson_offers_a_fixed_count_in_another_order():
    t = {"arrivals": "poisson", "rate_per_s": 100.0}
    a = load.arrival_offsets(t, 1, 10.0)
    b = load.arrival_offsets(t, 1, 10.0)
    c = load.arrival_offsets(t, 2, 10.0)
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == 1000 and not np.array_equal(a, c)
    assert (np.diff(a) >= 0).all() and 0 <= a[0] and a[-1] < 10.0


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        load.arrival_offsets({"arrivals": "zipf", "rate_per_s": 1}, 0, 1.0)


# -- windows: a rate over whole solves, a tail over all requests -------------

class _Clock:
    """A fake ``time`` for the drivers: sleeping advances it."""

    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        return self.t

    perf_counter = monotonic

    def sleep(self, s):
        self.t += s


def _cell(traffic, hooks, config=None):
    return harness.Cell(workload={"chips": 1}, config=config or {
        "generator": GEN, "engine": {"method": "boruvka", "params": {}}},
        traffic=traffic, seed=1, family=RMAT, hooks=hooks)


def test_rate_is_taken_over_whole_solves(monkeypatch):
    mod = registry.driver("solve_loop")
    clock = _Clock()
    monkeypatch.setattr(mod, "time", clock)

    def solver(drv):                         # every solve takes 0.3 s
        clock.sleep(0.3)
        return np.zeros(drv.edges.num_edges, bool), 4

    drv = mod.Driver(_cell({}, {"solver": solver}))
    drv.edges = types.SimpleNamespace(num_edges=1000)
    win = drv.window(1.0, lambda name: _null())
    # Four whole solves: the one that crosses 1.0 s is measured to its end.
    assert win.attempted == 4 and win.window_s == pytest.approx(1.2)
    assert win.e2e["edges_per_s"] == pytest.approx(4000 / 1.2)
    assert win.counters["rounds"] == 16
    win = drv.window(0.1, lambda name: _null())
    assert win.attempted == 1 and win.window_s == pytest.approx(0.3)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class _SheddingService:
    """Sheds every 10th request; resolves the rest 5 ms after arrival."""

    def __init__(self, clock, shed_error):
        self.clock, self.shed_error = clock, shed_error
        self.stats = types.SimpleNamespace(size_flushes=0,
                                           deadline_flushes=0,
                                           drain_flushes=0)
        self.k, self.pending = 0, []

    def submit(self, graph, *, t_arrival=None):
        from concurrent.futures import Future
        self.k += 1
        if self.k % 10 == 0:
            raise self.shed_error("full")
        f = Future()
        self.pending.append((t_arrival + 0.005, f))
        return f

    def poll(self, now=None):
        keep = []
        for due, f in self.pending:
            if self.clock.t >= due:
                f.set_result(types.SimpleNamespace(edge_mask=[]))
                self.stats.deadline_flushes += 1
            else:
                keep.append((due, f))
        self.pending = keep


def test_tail_is_over_all_requests_with_sheds_counted(monkeypatch):
    mod = registry.driver("open_loop")
    clock = _Clock()
    monkeypatch.setattr(mod, "time", clock)
    traffic = {"arrivals": "poisson", "rate_per_s": 100.0}
    drv = mod.Driver(_cell(traffic, {}))
    drv.offsets = load.arrival_offsets(traffic, 1, 1.0)
    drv.graphs = [None] * len(drv.offsets)
    drv.requests = [None] * len(drv.offsets)
    drv.shed_error = RuntimeError
    drv.service = _SheddingService(clock, RuntimeError)
    win = drv.window(1.0, lambda name: _null())
    assert win.attempted == 100 and win.counters["accepted"] == 90
    served = [(d - t) * 1e3 for d, t in zip(drv.done_at, 1000.0 + drv.offsets)
              if d is not None]
    assert len(served) == 90 and max(served) < 7.0   # 5 ms and a poll step
    # Ten shed requests (10%) make the tail: p95 is a shed one, which
    # counts from its arrival to the end of the wait.
    assert win.e2e["latency_ms_p95"] > 7.0
    assert win.e2e["latency_ms_p50"] < 7.0
    assert sum(f is None for f in drv.futures) == 10


# -- finding parts by name ---------------------------------------------------

def test_new_parts_are_found_by_name_without_editing(tmp_path):
    """A later PR adds a configuration, a traffic mix and a metric as new
    files and entries; nothing that exists changes."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "configs" / "gnm_small.json").write_text(json.dumps(
        {"family": "rmat", "generator": GEN,
         "engine": {"method": "boruvka", "params": {}}}))
    (bench_dir / "traffic" / "slow.json").write_text(json.dumps(
        {"driver": "open_loop", "arrivals": "poisson", "rate_per_s": 5}))
    (bench_dir / "metrics" / "solves_per_s.py").write_text(
        "def read(rec):\n    return rec.window.attempted / "
        "rec.window.window_s\n")
    bench = {
        "configs": [{"name": "gnm_small",
                     "file": "bench/configs/gnm_small.json"}],
        "workloads": [{"name": "gnm.burst", "config": "gnm_small",
                       "traffic": "slow", "chips": 1}],
        "end_to_end": [{"name": "setup_s"},
                       {"name": "edges_per_s", "workloads": ["other"]}],
        "per_layer": [{"name": "solves_per_s", "moves": "setup_s"},
                      {"name": "round_ms", "moves": "edges_per_s"}]}
    wl = registry.workload(bench, "gnm.burst")
    assert registry.config(bench, wl["config"], str(tmp_path))["family"] \
        == "rmat"
    assert registry.traffic("slow", str(bench_dir))["rate_per_s"] == 5
    read = registry.metric_reader("solves_per_s", str(bench_dir))
    rec = types.SimpleNamespace(window=harness.Window(
        window_s=2.0, attempted=6, e2e={}))
    assert read(rec) == 3.0
    e2e, layer = registry.cell_metrics(bench, "gnm.burst")
    assert [m["name"] for m in e2e] == ["setup_s"]
    assert [m["name"] for m in layer] == ["solves_per_s"]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_names_are_plain_file_stems():
    with pytest.raises(ValueError):
        registry.traffic("../BENCHMARK")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("no_such_metric")


def test_every_benchmark_entry_has_its_file():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        cfg = registry.config(bench, w["config"])
        traffic = registry.traffic(w["traffic"])
        registry.family(cfg["family"])
        registry.driver(traffic["driver"])
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_no_accelerator_is_refused():
    import jax
    with pytest.raises(harness.NoDevice):
        harness.require_devices(jax, 1)
    fake = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite")])
    with pytest.raises(harness.NoDevice, match="4 chips"):
        harness.require_devices(fake, 4)


def test_run_py_without_a_chip_prints_no_result():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rmat20.solve", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
    assert time.monotonic() - t0 < 300


def test_run_py_without_the_program_prints_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import subprocess
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat20.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no program" in out.stderr

"""The GRID2D cell's parts at toy sizes, on the CPU: the benchmark's copy
of the lattice against the program's, the ``shortcut_share`` reader, and
whole runs of ``grid2d.solve`` with the control and the faults."""
import copy
import json
import os
import sys
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import controls, harness, registry, trace  # noqa: E402

GRID = registry.family("grid2d")
GEN = {"scale": 9, "graph_seed": 0}
SEED = 2**31 + 1617
SHORTCUT = registry.metric_reader("shortcut_share")


def _spec(gen):
    from repro.core import pipeline
    return pipeline.GraphSpec("grid2d", gen["scale"], seed=gen["graph_seed"])


@pytest.mark.parametrize("graph_seed", [0, 2**31 + 5])
def test_grid2d_reference_graph_is_the_programs_graph(graph_seed):
    """The benchmark's copy of the lattice, the weight draw and §3.1 gives
    the graph the program's own host path (``build_host``) gives."""
    from repro.core import pipeline
    gen = dict(GEN, graph_seed=graph_seed)
    n, src, dst, w = GRID.reference_graph(gen)
    g = pipeline.build_host(_spec(gen))
    assert n == g.num_vertices == 512 and (src < dst).all()
    assert len(src) == 32 * 15 + 31 * 16          # 16 columns, 32 rows
    np.testing.assert_array_equal(src, g.src)
    np.testing.assert_array_equal(dst, g.dst)
    np.testing.assert_array_equal(w.view(np.uint32), g.weight.view(np.uint32))


def test_grid2d_device_build_matches_host_graph():
    n, src, dst, w = GRID.reference_graph(GEN)
    edges = GRID.program_input(GEN)
    assert edges.num_edges == len(src) and edges.capacity == 1024
    g = edges.to_graph()
    np.testing.assert_array_equal(g.src, src)
    np.testing.assert_array_equal(g.dst, dst)
    np.testing.assert_array_equal(g.weight.view(np.uint32), w.view(np.uint32))


def test_grid2d_weights_keep_their_ties():
    _, _, _, w = GRID.reference_graph(dict(GEN, scale=14))
    assert np.unique(w).size < w.size


# -- shortcut_share ----------------------------------------------------------

def _record(op_s):
    summary = trace.TraceSummary(window_s=10.0, busy_s=9.0, chips=1,
                                 op_s=op_s, scatter_s=0.0, gaps=[], ops=5)
    return types.SimpleNamespace(trace=summary)


def test_shortcut_share_reads_pointer_doubling_over_op_time():
    rec = _record({"pointer_double/gather": 2.0, "pointer_double/add": 0.5,
                   "gather": 5.0, "segment_min64/scatter-min": 2.5})
    assert SHORTCUT(rec) == pytest.approx(25.0)


def test_shortcut_share_reads_nothing_without_its_ops():
    assert SHORTCUT(types.SimpleNamespace(trace=None)) is None
    # A program that names no pointer_double op (before it had a jit of
    # its own) reads nothing rather than 0.
    assert SHORTCUT(_record({"gather": 5.0, "scatter-min": 1.0})) is None


def test_pointer_double_gathers_get_their_own_kind():
    event = {"name": "gather.7", "args": {"tf_op": (
        "jit(<unknown>)/while/body/merge/jit(pointer_double)/while/body/"
        "closed_call/gather:")}}
    assert trace.op_kind(event) == "pointer_double/gather"


# -- whole runs at a toy size ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json as committed, the lattice cut to 2**11 vertices."""
    root = tmp_path_factory.mktemp("tiny_grid2d")
    bench = registry.load_benchmark()
    for c in bench["configs"]:
        cfg = registry.load_json(os.path.join(registry.ROOT, c["file"]))
        if cfg["family"] == "grid2d":
            cfg["generator"]["scale"] = 11
        c["file"] = f"{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), bench


def _run(tiny_root, hooks):
    root, bench = tiny_root
    return harness.run_cell(copy.deepcopy(bench), "grid2d.solve", SEED, 0.3,
                            False, t_start=time.monotonic(), root=root,
                            hooks=hooks, require_device=False)


def test_grid2d_program_is_correct(tiny_root):
    res = _run(tiny_root, {})
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["checks"] == {"wrong_edges": {"value": 0, "limit": 0}}
    assert set(res["metrics"]) == {"setup_s", "edges_per_s"}
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("hooks", [
    lambda: controls.control_hooks("solve_loop"),
    lambda: controls.fault_hooks("solve_loop", "altered"),
    lambda: controls.fault_hooks("solve_loop", "unchanged"),
], ids=["control", "altered", "unchanged"])
def test_grid2d_control_and_faults_are_not_correct(tiny_root, hooks):
    res = _run(tiny_root, hooks())
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"]["wrong_edges"]["value"] > 0

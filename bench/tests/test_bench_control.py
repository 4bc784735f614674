"""Whole runs of both cells at toy sizes on the CPU (the look for a chip
skipped): the program reads ``correct``, and the control and every fault
the cell can have read not correct."""
import copy
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import controls, harness, registry  # noqa: E402

SEED = 2**31 + 4321


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json as committed, its configurations cut to toy sizes."""
    root = tmp_path_factory.mktemp("tiny")
    bench = registry.load_benchmark()
    for c in bench["configs"]:
        cfg = registry.load_json(os.path.join(registry.ROOT, c["file"]))
        if cfg["family"] == "rmat":
            cfg["generator"]["scale"] = 11
        else:
            cfg["generator"].update(min_nodes=6, max_nodes=12, k=3)
            cfg["engine"]["params"].update(
                serve_lanes=2, serve_max_queue=8, batch_max_vertices=16,
                batch_max_edges=64)
        c["file"] = f"{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), bench


def _run(tiny_root, workload, hooks, trace=False):
    root, bench = tiny_root
    return harness.run_cell(copy.deepcopy(bench), workload, SEED, 0.3, trace,
                            t_start=time.monotonic(), root=root,
                            hooks=hooks, require_device=False)


@pytest.fixture
def short_wait(monkeypatch):
    monkeypatch.setattr(registry.driver("open_loop"), "WAIT_AFTER_CLOSE_S",
                        0.5)


@pytest.mark.parametrize("workload", ["rmat20.solve", "tenants.poisson"])
def test_program_is_correct(tiny_root, workload):
    res = _run(tiny_root, workload, {})
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("workload,driver", [
    ("rmat20.solve", "solve_loop"), ("tenants.poisson", "open_loop")])
def test_control_is_not_correct(tiny_root, workload, driver):
    res = _run(tiny_root, workload, controls.control_hooks(driver))
    assert res["correct"] is False and res["failed"] > 0


@pytest.mark.parametrize("workload,driver,fault", [
    ("rmat20.solve", "solve_loop", "altered"),
    ("rmat20.solve", "solve_loop", "unchanged"),
    ("tenants.poisson", "open_loop", "altered"),
    ("tenants.poisson", "open_loop", "half_left_out"),
])
def test_fault_is_not_correct(tiny_root, short_wait, workload, driver, fault):
    res = _run(tiny_root, workload, controls.fault_hooks(driver, fault))
    assert res["correct"] is False and res["failed"] > 0
    bad = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    want = {"half_left_out": ["unanswered"], "altered": None,
            "unchanged": ["wrong_edges"]}[fault]
    assert bad == (want or bad) and bad


def test_every_cell_has_a_control_and_faults():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        driver = registry.traffic(w["traffic"])["driver"]
        assert controls.control_hooks(driver)
        assert controls.FAULTS[driver]

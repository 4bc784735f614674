"""Find a cell's parts by name.

``BENCHMARK.json`` names every configuration, traffic mix and metric; each
lives in a file of its own, found here by that name:

* configuration ``<c>``   -> the ``file`` its ``configs`` entry gives
  (``bench/configs/<c>.json``);
* traffic mix ``<t>``     -> ``bench/traffic/<t>.json`` (data only);
* per-layer metric ``<m>`` -> ``bench/metrics/<m>.py`` (a reader with
  ``read(record) -> float | None``);
* graph family ``<f>`` (a configuration's ``family``) ->
  ``bench/families/<f>.py``;
* driver ``<d>`` (a traffic mix's ``driver``) -> ``bench/drivers/<d>.py``.

Adding a cell, a mix or a metric is adding files and entries; no existing
file changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def check_name(name: str) -> str:
    """A name may only be a plain file stem: no slash, no space."""
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"not a valid benchmark name: {name!r}")
    return name


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "traffic",
                                  check_name(name) + ".json"))


def _load_module(kind: str, name: str, bench_dir: str):
    path = os.path.join(bench_dir, kind, check_name(name) + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    mod_name = f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    mod = sys.modules.get(mod_name)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    return _load_module("metrics", name, bench_dir).read


def family(name: str, bench_dir: str = BENCH_DIR):
    return _load_module("families", name, bench_dir)


def driver(name: str, bench_dir: str = BENCH_DIR):
    return _load_module("drivers", name, bench_dir)


def applies(metric: dict, workload_name: str, cell_e2e: set) -> bool:
    """Whether ``metric`` is reported in ``workload_name``.

    With a ``workloads`` key, exactly the cells it lists.  Without one, an
    end-to-end metric is in every cell, and a per-layer metric is in every
    cell that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return workload_name in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in cell_e2e
    return True


def cell_metrics(bench: dict, workload_name: str):
    """(end-to-end metrics, per-layer metrics) that this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if applies(m, workload_name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m, workload_name, names)]
    return e2e, layer

"""Quickstart: build a graph, run both MST engines, check against Kruskal.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import platform
from repro.core import generators, kruskal_ref
from repro.core.mst_api import minimum_spanning_forest
from repro.core.params import GHSParams


def main():
    platform.use_compile_cache()
    # An RMAT graph, paper-style: SCALE=10 (1024 vertices), avg degree 32.
    g = generators.generate("rmat", 10, seed=42)
    print(f"graph: {g.num_vertices} vertices, {g.num_edges} edges")

    oracle = kruskal_ref.kruskal(g)
    print(f"kruskal oracle : weight={oracle.total_weight:.4f} "
          f"components={oracle.num_components}")

    forest, stats = minimum_spanning_forest(g, method="boruvka")
    print(f"optimized      : weight={forest.total_weight:.4f} "
          f"rounds={stats.rounds} "
          f"exact_match={np.array_equal(forest.edge_mask, oracle.edge_mask)}")

    forest, stats = minimum_spanning_forest(
        g, method="ghs", params=GHSParams(check_frequency=1))
    print(f"faithful GHS   : weight={forest.total_weight:.4f} "
          f"supersteps={stats.supersteps} msgs={stats.processed} "
          f"exact_match={np.array_equal(forest.edge_mask, oracle.edge_mask)}")


if __name__ == "__main__":
    main()

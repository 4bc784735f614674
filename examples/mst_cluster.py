"""Distributed MST across shard_map shards — the paper's experiment in
miniature (run with forced host devices to emulate a small cluster):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/mst_cluster.py --shards 8
"""
import argparse
import time

import jax
import numpy as np

from repro import platform
from repro.core import generators, kruskal_ref
from repro.core.mst_api import minimum_spanning_forest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=len(jax.devices()))
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--kind", default="rmat")
    ap.add_argument("--method", default="boruvka", choices=["boruvka", "ghs"])
    args = ap.parse_args()
    platform.use_compile_cache()

    mesh = None
    if args.shards > 1:
        from repro.compat import make_mesh
        mesh = make_mesh((args.shards,), ("x",))
    g = generators.generate(args.kind, args.scale, seed=7)
    print(f"{args.kind}-{args.scale}: {g.num_vertices} vertices, "
          f"{g.num_edges} edges on {args.shards} shard(s)")
    t0 = time.perf_counter()
    forest, stats = minimum_spanning_forest(g, method=args.method, mesh=mesh)
    dt = time.perf_counter() - t0
    oracle = kruskal_ref.kruskal(g)
    print(f"{args.method}: {dt:.2f}s weight={forest.total_weight:.4f} "
          f"exact={np.array_equal(forest.edge_mask, oracle.edge_mask)} "
          f"stats={stats}")


if __name__ == "__main__":
    main()

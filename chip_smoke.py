#!/usr/bin/env python3
"""Chip smoke test: the exact minimum spanning forest (MSF) main path, end
to end, on one TPU chip (or on four with ``--chips 4``).

Run from the root of a checkout, as one process that starts no other:

    python3 chip_smoke.py               # one chip, rmat scale 21
    python3 chip_smoke.py --chips 4     # the four-chip mesh phase only

One chip runs six phases through the public entry points
(``repro.core.mst_api`` and ``repro.launch.serve.MSTService``) on a Graph500
Kronecker graph built on the device (R-MAT a=.57 b=.19 c=.19, edge factor
16; scale 21 by default, 2**21 vertices and 2**25 sampled edges — see
``WHY_SCALE``):

1. ``boruvka``        Borůvka with default params (XLA round body);
2. ``boruvka_pallas`` the fused round with the Pallas election kernel;
3. ``filter_boruvka`` the sample/filter hybrid;
4. ``ghs``            the paper's message-driven GHS, at a small scale;
5. ``batch``/``serve`` a bucket of mixed-size graphs, then a served stream;
6. ``incremental``    one insert/delete batch on the large forest.

Each phase prints one ``phase=...`` line: whether its forest equals the
numpy oracle bit for bit (``kruskal_ref.boruvka_numpy`` over
``pipeline.build_host``, or ``kruskal`` on small graphs), wall seconds,
compile seconds (XLA backend compiles, from JAX's monitoring events),
solve seconds (wall minus compile: device work, tracing and host glue) and
the device's
``peak_bytes_in_use`` so far.  Times are of one cold run.  The oracle runs
on a host thread beside the device work.

Any mismatch or exception exits non-zero before the result line.  The
script refuses to run anywhere but on a TPU: it pins no platform and never
falls back to the CPU.  The last line of standard output is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SCALE = 21
WHY_SCALE = (
    "time, not memory. A cold scale-22 run on one v5e was cut at 1300 s "
    "inside phase 6 (phases 1-5 took 1,180 s of wall time, though "
    "phase 1's 478 s also held the wait for the host oracle). Scaling the "
    "cold scale-20 phase times by the growth measured from scale 20 to 22 "
    "(4.0x for the Pallas round) projects about 1,220-1,240 s for scale "
    "22, past the 1200 s limit. Memory fits: the largest scale-22 "
    "program, the build, compiles to 8.4 GiB of 16 GB")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPRESSED_CHECK = 3    # rounds per interval of the 4-chip compressed solve


class Background:
    """A host thread whose result (or exception) is collected later."""

    def __init__(self, fn, *args):
        self._out, self._err = None, None
        self._thread = threading.Thread(target=self._run, args=(fn, *args),
                                        daemon=True)
        self._thread.start()

    def _run(self, fn, *args):
        try:
            self._out = fn(*args)
        except Exception as e:              # re-raised in result()
            self._err = e

    def result(self):
        self._thread.join()
        if self._err is not None:
            raise self._err
        return self._out


class Meter:
    """Compile time from JAX's monitoring events, device peak memory."""

    def __init__(self, jax, device):
        self.device = device
        self.compile_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1

    def peak_bytes(self):
        stats = self.device.memory_stats() or {}
        return stats.get("peak_bytes_in_use", "not_reported")

    def run(self, name, fn):
        """Run one phase; print its line; raise unless it was exact.

        ``fn() -> (check, extra)``: the clock stops when ``fn`` returns,
        then ``check()`` compares with the oracle (waiting for the host
        thread if need be) and returns ``(exact, more_extra)``."""
        c0, n0, t0 = self.compile_s, self.compiles, time.perf_counter()
        check, extra = fn()
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        exact, more = check()
        extra.update(more)
        # exact=None: a set-up step whose output a later phase checks.
        fields = dict(phase=name,
                      exact="n/a" if exact is None else bool(exact),
                      wall_s=wall, compile_s=comp, solve_s=wall - comp,
                      compiles=self.compiles - n0,
                      peak_bytes_in_use=self.peak_bytes(), **extra)
        print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)
        if exact is False:
            raise SystemExit(f"chip_smoke: phase {name} is not exact")


def same_graph(a, b) -> bool:
    import numpy as np
    return (a.num_vertices == b.num_vertices
            and np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
            and np.array_equal(a.weight.view(np.uint32),
                               b.weight.view(np.uint32)))


def update_batch(graph, tree_mask, seed: int, size: int = 4096):
    """A seeded insert/delete batch: half deletes (two thirds of them tree
    edges, so the cut probe has replacements to find), half inserts of
    random pairs with (0, 1) weights."""
    import numpy as np
    from repro.core import incremental
    rng = np.random.default_rng(seed)
    tree = np.flatnonzero(tree_mask)
    rest = np.flatnonzero(~tree_mask)
    k = size // 2
    k_tree = min(2 * k // 3, tree.size // 2)
    k_rest = min(k - k_tree, rest.size // 2)
    dels = np.concatenate([rng.choice(tree, k_tree, replace=False),
                           rng.choice(rest, k_rest, replace=False)])
    n = graph.num_vertices
    w = (rng.integers(0, 1 << 23, k) + 0.5) * 2.0 ** -23
    return incremental.EdgeBatch(
        insert_src=rng.integers(0, n, k).astype(np.int32),
        insert_dst=rng.integers(0, n, k).astype(np.int32),
        insert_weight=w.astype(np.float32),
        delete_src=graph.src[dels], delete_dst=graph.dst[dels])


def forest_oracle(spec):
    """numpy build + numpy Borůvka of ``spec``: the independent reference."""
    from repro.core import kruskal_ref, pipeline
    g = pipeline.build_host(spec)
    return g, kruskal_ref.boruvka_numpy(g)


def update_oracle(first, seed):
    """The phase-6 batch, drawn from the reference forest, and the
    reference forest of the updated graph.  The updated graph comes from
    the preprocess-based definition, not the sorted merge that
    ``apply_updates`` runs."""
    from repro.core import incremental, kruskal_ref
    g, f = first.result()
    batch = update_batch(g, f.edge_mask, seed)
    g2 = incremental._apply_edge_batch_reference(g, batch)
    return batch, g2, kruskal_ref.boruvka_numpy(g2)


def one_chip(args, meter):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import boruvka_dist, kruskal_ref, mst_api, pipeline
    from repro.core.params import DEFAULT_PARAMS
    from repro.launch import serve

    spec = pipeline.GraphSpec("rmat", args.scale, avg_degree=32,
                              seed=args.seed)
    first = Background(forest_oracle, spec)
    second = Background(update_oracle, first, args.seed)
    box = {}

    def build():
        box["edges"] = e = pipeline.build(spec)
        jax.block_until_ready((e.src, e.dst, e.key))
        # Byte identity with the numpy build is checked in phase boruvka.
        return lambda: (None, {}), dict(
            scale=spec.scale, vertices=e.num_vertices, edges=e.num_edges,
            capacity=e.capacity)

    meter.run("build", build)
    edges = box["edges"]

    def same_forest(res, more=dict):
        def check():
            extra = more()
            want = first.result()[1].edge_mask
            return (all(extra.values())
                    and np.array_equal(res.edge_mask, want)), extra
        return check

    def msf(method, params=DEFAULT_PARAMS, more=dict):
        def fn():
            res, st = mst_api.minimum_spanning_forest(edges, method=method,
                                                      params=params)
            return same_forest(res, more), dict(
                tree_edges=res.num_tree_edges, rounds=st.rounds,
                intervals=st.intervals, host_syncs=st.host_syncs)
        return fn

    def build_identical():
        return dict(build_byte_identical=same_graph(edges.to_graph(),
                                                    first.result()[0]))

    meter.run("boruvka", msf("boruvka", more=build_identical))

    pallas = dataclasses.replace(DEFAULT_PARAMS, round_kernel="pallas",
                                 use_pallas=True)

    def kernel_in_program():
        # The interval program the fused Pallas round dispatched (the same
        # cached jit object), lowered again from its first call's shapes.
        n, m, cap = edges.num_vertices, edges.num_edges, edges.capacity
        sds = jax.ShapeDtypeStruct
        fn = boruvka_dist._build_interval_fn_fused(None, "pallas", None,
                                                    "pmin")
        with jax.enable_x64():
            text = fn.lower(
                sds((n,), jnp.uint32), sds((m,), jnp.bool_),
                sds((cap,), jnp.int32), sds((cap,), jnp.int32),
                sds((cap,), jnp.uint64), sds((m,), jnp.int32),
                sds((m,), jnp.int32), sds((), jnp.int32)).as_text()
        return dict(tpu_custom_call="tpu_custom_call" in text)

    meter.run("boruvka_pallas",
              msf("boruvka", pallas, more=kernel_in_program))
    meter.run("filter_boruvka", msf("filter_boruvka"))

    def ghs():
        g = pipeline.build_host(pipeline.GraphSpec("rmat", args.ghs_scale,
                                                   seed=args.seed))
        res, st = mst_api.minimum_spanning_forest(g, method="ghs")
        return lambda: (np.array_equal(
            res.edge_mask, kruskal_ref.kruskal(g).edge_mask), {}), dict(
                scale=args.ghs_scale, edges=g.num_edges,
                supersteps=st.supersteps, intervals=st.intervals)

    meter.run("ghs", ghs)

    def all_exact(graphs, forests):
        return lambda: (all(
            f is not None and np.array_equal(
                f.edge_mask, kruskal_ref.kruskal(g).edge_mask)
            for g, f in zip(graphs, forests)), {})

    def batch():
        # Mixed kinds and sizes at 1,024 vertices: 4k-9k edges, so the
        # graphs share one or two pow2 buckets.
        graphs = [pipeline.build_host(pipeline.GraphSpec(
            ("rmat", "random", "geo_knn")[i % 3], 10,
            avg_degree=8 + 2 * (i % 4), seed=args.seed + i))
            for i in range(16)]
        forests, st = mst_api.minimum_spanning_forests(graphs)
        return all_exact(graphs, forests), dict(
            graphs=len(graphs), buckets=st.buckets,
            edges=sum(g.num_edges for g in graphs), intervals=st.intervals)

    meter.run("batch", batch)

    def served():
        # A small lattice (<= 32 vertices, <= 128 edges, 1 or 2 lanes):
        # warmup compiles every shape it can dispatch.
        params = dataclasses.replace(
            DEFAULT_PARAMS, serve_lanes=2, batch_max_vertices=32,
            batch_max_edges=128)
        service = serve.MSTService(params)
        t0 = time.perf_counter()
        warmed = service.warmup()
        warmup_s = time.perf_counter() - t0
        rng = np.random.default_rng(args.seed)
        graphs = [pipeline.build_host(pipeline.GraphSpec(
            "rmat", int(rng.integers(2, 6)), avg_degree=8,
            seed=args.seed + i)) for i in range(32)]
        futures = serve.run_poisson(service, graphs, rate=500.0,
                                    seed=args.seed)
        forests = [f.result() if f is not None else None for f in futures]
        s = service.stats
        return all_exact(graphs, forests), dict(
            requests=len(graphs), completed=s.completed,
            warmup_s=warmup_s, buckets_warmed=warmed,
            p50_ms=s.percentile(50), p99_ms=s.percentile(99))

    meter.run("serve", served)

    def updates():
        batch = second.result()[0]
        state, _ = mst_api.incremental_forest(edges)
        state2, st = mst_api.apply_updates(state, batch)

        def check():
            _, graph2, forest2 = second.result()
            return (same_graph(state2.graph, graph2)
                    and np.array_equal(state2.forest.edge_mask,
                                       forest2.edge_mask)), {}
        return check, dict(updates=batch.size,
                           updates_applied=st.updates_applied,
                           replacement_probes=st.replacement_probes,
                           candidates=st.candidate_count)

    meter.run("incremental", updates)


def four_chips(args, meter):
    """Borůvka on a 4-device mesh under both cross-shard collectives."""
    import numpy as np
    from repro import compat
    from repro.core import mst_api, pipeline
    from repro.core.params import DEFAULT_PARAMS

    spec = pipeline.GraphSpec("rmat", args.scale, avg_degree=32,
                              seed=args.seed)
    oracle = Background(forest_oracle, spec)
    mesh = compat.make_mesh((4,), ("x",))
    box = {}

    def build():
        box["edges"] = e = pipeline.build(spec, mesh)
        shards = e.key.addressable_shards
        spread = dict(
            devices=len(e.key.sharding.device_set), shards=len(shards),
            shard_len_ok=all(s.data.shape[0] == e.capacity // 4
                             for s in shards))
        return lambda: (spread["devices"] == spread["shards"] == 4
                        and spread["shard_len_ok"], spread), dict(
            scale=spec.scale, edges=e.num_edges, capacity=e.capacity,
            shard_len=shards[0].data.shape[0])

    meter.run("build_4chip", build)
    # Under "compressed", intervals of COMPRESSED_CHECK rounds give the
    # host a fragment census early enough that later intervals pick the
    # ppermute ring over dense pmin (DESIGN.md §11 byte model).
    for collective, every in (("pmin", DEFAULT_PARAMS.check_frequency),
                              ("compressed", COMPRESSED_CHECK)):
        params = dataclasses.replace(DEFAULT_PARAMS, collective=collective,
                                     check_frequency=every)

        def solve():
            res, st = mst_api.minimum_spanning_forest(
                box["edges"], method="boruvka", params=params, mesh=mesh)
            ring = sum(r for mode, _, r, _ in st.comm_history
                       if mode == "compressed")
            box[collective] = st.comm_bytes

            def check():
                exact = np.array_equal(res.edge_mask,
                                       oracle.result()[1].edge_mask)
                if collective == "pmin":
                    return exact, {}
                ran = ring > 0 and st.comm_bytes < box["pmin"]
                return exact and ran, dict(ring_ran=ran)
            return check, dict(
                collective=collective, check_frequency=every,
                rounds=st.rounds, ring_rounds=ring,
                intervals=st.intervals, comm_bytes=st.comm_bytes,
                comm_history=",".join(f"{m}:{c}:{r}"
                                      for m, c, r, _ in st.comm_history),
                staging=st.edge_staging)

        meter.run(f"boruvka_4chip_{collective}", solve)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    ap.add_argument("--scale", type=int, default=SCALE,
                    help=f"rmat scale of the large graph (default {SCALE})")
    ap.add_argument("--ghs-scale", type=int, default=12,
                    help="rmat scale of the GHS phase (default 12)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if not os.path.isfile(os.path.join(src, "repro", "core", "mst_api.py")):
        sys.exit("chip_smoke: run from a checkout: no src/repro next to it")
    sys.path.insert(0, src)
    from repro import platform as platform_lib
    platform_lib.use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
                 "refusing to run anywhere else")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if args.scale == SCALE:
        print(f"scale {SCALE}, not 22: {WHY_SCALE}", flush=True)

    meter = Meter(jax, dev)
    if args.chips == 4:
        four_chips(args, meter)
    else:
        one_chip(args, meter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

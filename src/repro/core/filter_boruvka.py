"""Filter-Borůvka sampling hybrid — expected-linear work (DESIGN.md §10).

Sample → solve → filter → solve, after Sanders & Schimek (*Engineering
Massively Parallel MST Algorithms*, PAPERS.md):

1. **Sample.**  A counter-based splitmix64 Bernoulli sample over canonical
   edge ids (:func:`repro.core.pipeline.sample_mask`) — a pure function of
   ``(pass, edge id)``, so the sample is byte-identical at any shard count
   and on either array namespace, like the graph generators.
2. **Solve the sample** with the existing Borůvka engine (every knob —
   partitioner, round_kernel, round_loop, mesh — composes unchanged).  Its
   forest ``F_S`` is the partial forest.
3. **Filter** (the cycle rule).  An edge ``e ∉ S`` is provably non-MSF iff
   its endpoints are connected in ``F_S`` using only tree edges with packed
   key strictly below ``key(e)`` — then ``e`` is the strict maximum of a
   cycle under the global (weight ‖ edge-id) total order of
   :mod:`repro.core.keys`, and the unique MSF excludes it.  Exact path
   maxima are priced out; instead the probe quantizes: sort the tree keys,
   take ``params.filter_levels`` quantile *thresholds* ``T_1 ≤ … ≤ T_K``,
   and build per-level fragment labels = connected components over tree
   edges with ``key ≤ T_j`` (one vmapped
   :func:`repro.kernels.spmv_minplus.ops.connected_labels` launch).  Drop
   ``e`` iff some ``T_j < key(e)`` connects its endpoints — since keys are
   globally distinct, connectivity at that level certifies a strictly
   lighter path.  Quantization only affects filter *efficiency* (how many
   droppable edges are recognized), never correctness.  Sampled non-tree
   edges are dropped outright (cycle property inside ``S ⊆ G``); sampled
   tree edges always survive.
4. **Final solve** over the survivors (partial forest included).  If the
   survivor count still exceeds ``params.filter_threshold`` (0 = auto,
   ``4·n``), one recursion — a second sample→solve→filter pass over the
   survivors under a fresh sample stream — runs first; never more
   (:data:`MAX_PASSES`).

Correctness is a subset sandwich: survivors always contain every MSF edge
(only provably-non-MSF edges are dropped) and are contained in the input,
and the MSF is unique under the packed-key total order — so the final
solve's forest is bit-identical to solving the full input, for every
sample rate, level count, and shard count.  The empty-sample guarantee is
the degenerate case: ``filter_sample_rate ≤ 0`` samples nothing, nothing
is filtered, and the final solve sees every edge.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.core import boruvka_dist
from repro.core import keys as keys_lib
from repro.core import partition as partition_lib
from repro.core import pipeline as pipeline_lib
from repro.core import runtime
from repro.core.graph import PAD_VERTEX, Graph
from repro.core.kruskal_ref import ForestResult
from repro.core.params import DEFAULT_PARAMS, GHSParams
from repro.kernels.spmv_minplus import ops as minplus_ops
from repro.sharding import collectives

MAX_PASSES = 2          # initial pass + the single recursion of DESIGN.md §10


@dataclasses.dataclass
class FilterStats(boruvka_dist.BatchStats):
    """Ledger of a filter-Borůvka run.

    ``edges_filtered`` / ``filter_passes`` (runtime protocol) meter the
    filter itself; the sub-solve counters (rounds, compactions, host syncs,
    …) accumulate across the sample and final solves through the inherited
    :meth:`~repro.core.boruvka_dist.BatchStats.merge`.
    ``survivor_history`` records the candidate count after each pass.
    """

    survivor_history: tuple = ()


def _thresholds(tree_keys: np.ndarray, num_levels: int) -> np.ndarray:
    """Ascending per-level key quantiles (upper edges) of the tree keys."""
    t_sorted = np.sort(tree_keys)
    t = t_sorted.size
    qi = (np.arange(1, num_levels + 1, dtype=np.int64) * t) // num_levels - 1
    return t_sorted[np.maximum(qi, 0)]


@functools.lru_cache(maxsize=None)
def _build_filter_fns(num_vertices: int, mesh: Optional[Mesh],
                      collective: str = "pmin",
                      cand_cap: Optional[int] = None):
    """Compiled (labels, probe) pair for one vertex count.

    ``labels`` builds the (K, n) per-level fragment labels from the padded
    tree arrays — one vmapped converged-connectivity launch, K lanes
    sharing a single compiled while_loop.  Under a mesh it runs
    tree-edge-sharded (labels replicated), and ``collective``/``cand_cap``
    route its per-iteration hook-min through the compressed delta exchange
    (DESIGN.md §11; cand_cap is pow2 so the cache stays log-bounded).
    ``probe`` evaluates the quantized cycle rule for every candidate edge;
    under a mesh it runs edge-sharded with the labels replicated.
    """
    n = num_vertices
    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1

    def labels_fn(t_src, t_dst, t_key, thresholds, axis_name=None):
        # Levels are nested (T_1 ≤ … ≤ T_K), so level j warm-starts from
        # level j-1's labels: only newly-activated tree edges pay hook
        # iterations, and the whole stack costs little more than one
        # converged solve.
        comp, rows = None, []
        for j in range(thresholds.shape[0]):
            comp = minplus_ops.connected_labels(
                t_src, t_dst, t_key <= thresholds[j], num_vertices=n,
                init=comp, axis_name=axis_name,
                collective=collective, cand_cap=cand_cap,
                num_shards=num_shards)
            rows.append(comp)
        return jnp.stack(rows)

    def probe_fn(labels, thresholds, src, dst, key, sampled, tree):
        # idx = #thresholds strictly below key(e): keys are globally
        # distinct from every tree key, so side="left" is a strict count.
        idx = jnp.searchsorted(thresholds, key, side="left")
        lvl = jnp.maximum(idx - 1, 0).astype(jnp.int64)
        u = jnp.clip(src, 0, n - 1).astype(jnp.int64)
        v = jnp.clip(dst, 0, n - 1).astype(jnp.int64)
        flat = labels.reshape(-1)
        below = (idx > 0) & (flat[lvl * n + u] == flat[lvl * n + v])
        return jnp.where(sampled, tree, ~below)

    if mesh is not None:
        labels_fn = compat.shard_map(
            functools.partial(labels_fn, axis_name="x"), mesh,
            in_specs=(P("x"), P("x"), P("x"), P()),
            out_specs=P())
        probe_fn = compat.shard_map(
            probe_fn, mesh,
            in_specs=(P(), P(), P("x"), P("x"), P("x"), P("x"), P("x")),
            out_specs=P("x"))
    return jax.jit(labels_fn), jax.jit(probe_fn)


def _pad_to(arrs, cap: int, fills):
    return tuple(
        np.concatenate([a, np.full(cap - a.size, f, a.dtype)])
        for a, f in zip(arrs, fills))


def _run_filter(g: Graph, cand: np.ndarray, tree_pos: np.ndarray,
                smask: np.ndarray, params: GHSParams,
                mesh: Optional[Mesh]) -> np.ndarray:
    """Keep-mask over ``cand`` from the quantized cycle rule (host glue)."""
    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    c_src, c_dst = g.src[cand], g.dst[cand]
    c_key = g.packed_keys[cand]
    tmask = np.zeros(cand.size, dtype=bool)
    tmask[tree_pos] = True

    thresholds = _thresholds(c_key[tree_pos], int(params.filter_levels))
    # Tree arrays are sharded under a mesh: a pow2 per-shard block keeps
    # every shard rectangular at any device count.
    t_block = partition_lib.pow2ceil(
        max(-(-max(tree_pos.size, 8) // num_shards), 1))
    t_cap = t_block * num_shards
    t_src, t_dst = _pad_to((c_src[tree_pos], c_dst[tree_pos]), t_cap,
                           (PAD_VERTEX, PAD_VERTEX))
    (t_key,) = _pad_to((c_key[tree_pos],), t_cap, (keys_lib.INF_KEY,))

    # Compressed hook-min exchange for the label loop (DESIGN.md §11):
    # each local tree edge can hook at most one entry per iteration, so
    # the per-shard block bounds the candidate count; engage only when the
    # wire model beats the dense uint32 pmin.
    n = g.num_vertices
    collective = runtime.resolve_collective(params.collective)
    cand_cap = None
    if num_shards > 1 and collective == "compressed":
        cap = max(partition_lib.pow2ceil(min(n, 2 * t_block)), 8)
        if (collectives.compressed_bytes(cap, num_shards, 4)
                < collectives.dense_bytes(n, num_shards, 4)):
            cand_cap = cap

    # Probe shape: power-of-two multiple of the shard count, padded with
    # INF keys (pad lanes resolve to "drop", then fall off the [:size]
    # slice below).
    m_cap = partition_lib.pow2ceil(max(cand.size, 8, num_shards))
    p_src, p_dst = _pad_to((c_src, c_dst), m_cap, (PAD_VERTEX, PAD_VERTEX))
    (p_key,) = _pad_to((c_key,), m_cap, (keys_lib.INF_KEY,))
    p_smp, p_tree = _pad_to((smask, tmask), m_cap, (False, False))

    labels_fn, probe_fn = _build_filter_fns(
        g.num_vertices, mesh,
        "compressed" if cand_cap is not None else "pmin", cand_cap)
    with jax.enable_x64():
        labels = labels_fn(jnp.asarray(t_src), jnp.asarray(t_dst),
                           jnp.asarray(t_key), jnp.asarray(thresholds))
        keep = probe_fn(labels, jnp.asarray(thresholds),
                        jnp.asarray(p_src), jnp.asarray(p_dst),
                        jnp.asarray(p_key), jnp.asarray(p_smp),
                        jnp.asarray(p_tree))
        keep = np.asarray(jax.device_get(keep), dtype=bool)[:cand.size]
    return keep


def minimum_spanning_forest(
    graph,
    params: GHSParams = DEFAULT_PARAMS,
    mesh: Optional[Mesh] = None,
    max_rounds: Optional[int] = None,
) -> tuple[ForestResult, FilterStats]:
    """Filter-Borůvka driver — same contract as the plain engine entry.

    ``graph`` is a host :class:`Graph` or a device-resident
    :class:`repro.core.pipeline.DeviceEdges`; the forest is bit-identical
    to ``method="boruvka"`` (and the Kruskal oracle) for every
    ``filter_sample_rate`` / ``filter_levels`` / shard count.
    """
    if not 1 <= int(params.filter_levels) <= 64:
        raise ValueError(
            f"filter_levels must be in [1, 64], got {params.filter_levels}")
    g = runtime.as_graph(graph)
    n, m = g.num_vertices, g.num_edges
    rate = float(params.filter_sample_rate)
    threshold = int(params.filter_threshold)
    if threshold <= 0:
        threshold = 4 * max(n, 1)

    stats = FilterStats()
    cand = np.arange(m, dtype=np.int64)          # canonical ids still in play

    for pass_idx in range(MAX_PASSES):
        smask = np.asarray(pipeline_lib.sample_mask(
            pass_idx, rate, cand.astype(np.uint64)), dtype=bool)
        s_pos = np.flatnonzero(smask)

        tree_pos = np.zeros(0, dtype=np.int64)
        if s_pos.size:
            # Canonical-subset order + monotone renumbering keep the
            # (weight, edge-id) election order, so the sample forest is the
            # true MSF of the sampled subgraph (partition.subgraph_by_mask
            # contract).
            sample_g = Graph(num_vertices=n, src=g.src[cand[s_pos]],
                             dst=g.dst[cand[s_pos]],
                             weight=g.weight[cand[s_pos]])
            f_s, st = boruvka_dist.minimum_spanning_forest(
                sample_g, params=params, mesh=mesh, max_rounds=max_rounds)
            stats.merge(st)
            tree_pos = s_pos[f_s.edge_mask]

        if tree_pos.size:
            keep = _run_filter(g, cand, tree_pos, smask, params, mesh)
            stats.host_syncs += 1      # keep-mask fetch inside _run_filter
            stats.extra_syncs += 1
        else:
            # Empty (or forest-free) sample: nothing is provably non-MSF,
            # so the final solve sees the full candidate set — the
            # empty-sample guarantee (DESIGN.md §10).
            keep = np.ones(cand.size, dtype=bool)

        stats.filter_passes += 1
        stats.edges_filtered += int(cand.size - keep.sum())
        cand = cand[keep]
        stats.survivor_history += (cand.size,)
        if cand.size <= threshold or not tree_pos.size or rate >= 1.0:
            break

    live = np.zeros(m, dtype=bool)
    live[cand] = True
    sub, index = partition_lib.subgraph_by_mask(g, live)
    res, st = boruvka_dist.minimum_spanning_forest(
        sub, params=params, mesh=mesh, max_rounds=max_rounds)
    stats.merge(st)

    forest = runtime.forest_from_mask(
        g, partition_lib.lift_mask(index, res.edge_mask, m),
        num_components=res.num_components)
    forest.check_consistent(n)
    return forest, stats

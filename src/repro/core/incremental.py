"""Incremental MST for evolving graphs — batched insert/delete (DESIGN.md §13).

Every engine solves from scratch; real serving traffic mutates graphs.
This module applies one :class:`EdgeBatch` of insertions and deletions to a
solved :class:`IncrementalForest` and returns the forest of the updated
graph, bit-identical to a from-scratch re-solve, at a fraction of the work.

The pass is the classical cycle/cut pair, made device-resident on the
existing fragment/label machinery (after *Time, Message and Memory-Optimal
Distributed MST and Partwise Aggregation*, Elkin & Goldenfeld, PAPERS.md —
partwise aggregation IS the cut/cycle verification primitive — and keeping
the probe batched rather than per-edge host logic after Sanders & Schimek,
PAPERS.md):

1. **Merge (host glue).**  :func:`apply_edge_batch` builds the updated
   graph deterministically: deletions remove canonical pairs, insertions
   run through the §3.1 preprocess semantics (self-loops dropped, per-pair
   minimum weight wins, ties keep the surviving copy).  This construction
   is the DEFINITION of the updated graph — the bit-identity reference is
   a full re-solve of exactly this graph.
2. **Anchor forest.**  ``F0`` = the old tree edges that survive unmodified
   (same pair, same weight).  A subset of a forest is a forest, and every
   F0 edge exists in the updated graph, so certificates built over F0 are
   certificates in the updated graph.
3. **Cycle probe (device).**  A non-F0 edge is provably non-MSF iff its
   endpoints connect through strictly lighter edges.  Two device
   certificates, both evaluated in the UPDATED graph's packed-key space
   (sound under weight ties, where re-keyed edge ids may flip old
   tie-breaks):

   * the quantized threshold-level probe of the filter pass — per-level
     fragment labels over F0 edges with key ≤ T_j, built by the
     warm-started :func:`repro.kernels.spmv_minplus.ops.connected_labels`
     hook/shortcut chain (level j refines level j-1's labels);
   * the packed max-key bound of
     :func:`repro.kernels.spmv_minplus.ops.component_maxkey` — the same
     loop warm-started from the top level's labels, returning each
     component's maximum tree key.  An edge inside one component whose key
     exceeds that bound exceeds its path max — the cycle rule's "swap
     against the max tree edge" test, with the swap resolved by the final
     solve over the kept candidates.

4. **Cut probe (device, same launch).**  Deleting a tree edge severs its
   component; replacement edges are exactly the non-F0 edges whose
   endpoints land in DIFFERENT F0 components (the probe's top-level
   labels).  They are never droppable by the cycle certificates, stay
   candidates, and the final solve elects the minimum crossing each cut —
   ``replacement_probes`` counts them.  One fused keep/cross-mask fetch is
   the single blocking readback of the whole update batch.
5. **Final solve.**  The Borůvka engine runs over the kept candidates
   (``F0`` + un-certified edges) via the §10 subset-graph path
   (``subgraph_by_mask`` / ``lift_mask`` keep the election order).  Since
   candidates ⊇ MSF(updated graph) and the MSF is unique under the packed
   (weight ‖ edge-id) total order, the lifted forest is bit-identical to
   the full re-solve — for every level count, shard count, and update mix.

:func:`plan_updates` / :func:`finalize_plan` split the pass around the
final solve so the serving layer (DESIGN.md §12) can batch many requests'
candidate solves through ``minimum_spanning_forests`` — each lane is
bit-identical to the single-graph solve, hence to :func:`apply_updates`.
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
from repro.core import runtime
from repro.core.filter_boruvka import _thresholds
from repro.core.graph import PAD_VERTEX, Graph, pair_ids, preprocess
from repro.core.kruskal_ref import ForestResult
from repro.core.params import DEFAULT_PARAMS, GHSParams
from repro.kernels.spmv_minplus import ops as minplus_ops
from repro.sharding import collectives


@dataclasses.dataclass(frozen=True)
class EdgeBatch:
    """One batched update: edge insertions (u, v, w) and deletions (u, v).

    Endpoints are vertex ids of the host graph (the vertex set is fixed —
    growing it is a new graph, not an update); insert weights must lie in
    the engines' (0, 1) range.  Deletions name canonical pairs — deleting
    a pair that is absent is a no-op, as is inserting a self-loop.  A pair
    both deleted and inserted in one batch is deleted from the OLD graph
    first, then re-inserted.
    """

    insert_src: np.ndarray     # (I,) int32
    insert_dst: np.ndarray     # (I,) int32
    insert_weight: np.ndarray  # (I,) float32, in (0, 1)
    delete_src: np.ndarray     # (D,) int32
    delete_dst: np.ndarray     # (D,) int32

    @classmethod
    def make(cls, inserts=(), deletes=()) -> "EdgeBatch":
        """Build from sequences of ``(u, v, w)`` / ``(u, v)`` tuples."""
        ins = np.asarray(list(inserts), dtype=np.float64).reshape(-1, 3)
        dels = np.asarray(list(deletes), dtype=np.int64).reshape(-1, 2)
        return cls(
            insert_src=ins[:, 0].astype(np.int32),
            insert_dst=ins[:, 1].astype(np.int32),
            insert_weight=ins[:, 2].astype(np.float32),
            delete_src=dels[:, 0].astype(np.int32),
            delete_dst=dels[:, 1].astype(np.int32),
        )

    @property
    def num_inserts(self) -> int:
        return int(self.insert_src.shape[0])

    @property
    def num_deletes(self) -> int:
        return int(self.delete_src.shape[0])

    @property
    def size(self) -> int:
        return self.num_inserts + self.num_deletes

    def validate(self, num_vertices: int) -> None:
        for a in (self.insert_src, self.insert_dst,
                  self.delete_src, self.delete_dst):
            if a.size and not (int(a.min()) >= 0
                               and int(a.max()) < num_vertices):
                raise ValueError(
                    f"update endpoints must lie in [0, {num_vertices})")
        w = self.insert_weight
        if w.size and not (float(w.min()) > 0.0 and float(w.max()) < 1.0):
            raise ValueError("insert weights must lie in (0, 1) — the "
                             "packed-key range of the engines (keys.py)")


@dataclasses.dataclass(frozen=True)
class IncrementalForest:
    """A solved graph: the handle :func:`apply_updates` evolves.

    ``forest.edge_mask`` indexes ``graph``'s canonical edges; after an
    update both are replaced (canonical ids shift when edges come and go),
    so hold on to the RETURNED handle, not the input one.
    """

    graph: Graph
    forest: ForestResult


@dataclasses.dataclass
class IncrementalStats(boruvka_dist.BatchStats):
    """Ledger of one :func:`apply_updates` batch.

    ``updates_applied`` / ``replacement_probes`` (runtime protocol) meter
    the update pass itself: structural changes actually applied (inserts
    that created or lightened an edge + deletes that removed one) and
    cut-probe candidates (non-tree edges crossing severed components).
    ``candidate_count`` is the final solve's edge count — the work the
    incremental pass did NOT skip; the sub-solve counters accumulate
    through the inherited :meth:`~repro.core.boruvka_dist.BatchStats.merge`.
    """

    candidate_count: int = 0


@dataclasses.dataclass(frozen=True)
class UpdatePlan:
    """The host-side residue of one probed update batch, ready for its
    final solve: the updated graph, the candidate subgraph (canonical
    subset — DESIGN.md §10 order contract), the lift index, and the probe
    ledger.  ``finalize_plan`` joins it with the candidate forest."""

    graph: Graph
    sub: Graph
    index: np.ndarray
    stats: IncrementalStats


def _canonical_pairs(src, dst, num_vertices: int) -> np.ndarray:
    u = np.minimum(src, dst).astype(np.int64)
    v = np.maximum(src, dst).astype(np.int64)
    return pair_ids(u, v, num_vertices)


def _apply_edge_batch_reference(graph: Graph, batch: EdgeBatch) -> Graph:
    """The DEFINITION of the updated graph: delete canonical pairs, then
    run everything back through §3.1 ``preprocess``.  Ties between an
    inserted copy and a surviving edge keep the survivor (the lexsort is
    stable and survivors precede inserts in the concatenation)."""
    n = graph.num_vertices
    keep = np.ones(graph.num_edges, dtype=bool)
    if batch.num_deletes:
        loops = batch.delete_src == batch.delete_dst
        dpid = np.unique(_canonical_pairs(
            batch.delete_src[~loops], batch.delete_dst[~loops], n))
        keep = ~np.isin(_canonical_pairs(graph.src, graph.dst, n), dpid)
    return preprocess(
        np.concatenate([graph.src[keep], batch.insert_src]),
        np.concatenate([graph.dst[keep], batch.insert_dst]),
        np.concatenate([graph.weight[keep], batch.insert_weight]),
        n)


def apply_edge_batch(graph: Graph, batch: EdgeBatch) -> Graph:
    """The updated graph — bit-identical to
    :func:`_apply_edge_batch_reference` (the preprocess-based definition),
    via a sorted merge: ``preprocess`` emits edges sorted by pair id, so
    deletions are a searchsorted mask and insertions splice in at their
    sorted positions — no O(m log m) lexsort of the whole survivor set per
    batch.  Collisions keep the minimum weight with ties going to the
    survivor, and duplicate inserts keep their first minimum copy, exactly
    matching the reference's stable sort.  A graph that is (somehow) not
    pair-sorted falls back to the reference path."""
    batch.validate(graph.num_vertices)
    n = graph.num_vertices
    pid = _canonical_pairs(graph.src, graph.dst, n)
    if pid.size and not bool(np.all(pid[1:] > pid[:-1])):
        return _apply_edge_batch_reference(graph, batch)
    src, dst, weight = graph.src, graph.dst, graph.weight

    if batch.num_deletes:
        loops = batch.delete_src == batch.delete_dst
        dpid = np.unique(_canonical_pairs(
            batch.delete_src[~loops], batch.delete_dst[~loops], n))
        if dpid.size:
            pos = np.searchsorted(dpid, pid)
            pos_c = np.minimum(pos, dpid.size - 1)
            keep = ~((pos < dpid.size) & (dpid[pos_c] == pid))
            src, dst = src[keep], dst[keep]
            weight, pid = weight[keep], pid[keep]

    if batch.num_inserts:
        iu = np.minimum(batch.insert_src, batch.insert_dst).astype(np.int64)
        iv = np.maximum(batch.insert_src, batch.insert_dst).astype(np.int64)
        iw = batch.insert_weight
        real = iu != iv                       # self-loops drop
        iu, iv, iw = iu[real], iv[real], iw[real]
        ipid = pair_ids(iu, iv, n)
        # Within-batch dedup: min weight per pair, first copy on weight
        # ties (np.lexsort is stable, matching the reference).
        order = np.lexsort((iw, ipid))
        ipid, iu, iv, iw = ipid[order], iu[order], iv[order], iw[order]
        first = np.ones(ipid.size, dtype=bool)
        first[1:] = ipid[1:] != ipid[:-1]
        ipid, iu, iv, iw = ipid[first], iu[first], iv[first], iw[first]
        # Collisions with survivors: strictly lighter inserts re-weight
        # the pair in place (ties keep the survivor).
        if pid.size:
            pos = np.searchsorted(pid, ipid)
            pos_c = np.minimum(pos, pid.size - 1)
            hit = (pos < pid.size) & (pid[pos_c] == ipid)
            lighter = hit & (iw < weight[pos_c])
            if lighter.any():
                weight = weight.copy()
                weight[pos_c[lighter]] = iw[lighter]
        else:
            pos = np.zeros(ipid.size, dtype=np.int64)
            hit = np.zeros(ipid.size, dtype=bool)
        # Fresh pairs splice in at their sorted positions.
        new = ~hit
        if new.any():
            at = pos[new]
            src = np.insert(src, at, iu[new].astype(np.int32))
            dst = np.insert(dst, at, iv[new].astype(np.int32))
            weight = np.insert(weight, at, iw[new])

    return Graph(num_vertices=n, src=src, dst=dst, weight=weight)


def _match_pairs(old: Graph, new: Graph) -> "tuple[np.ndarray, np.ndarray]":
    """Per-new-edge join against the old graph's canonical pairs:
    ``(hit, old_idx)`` with ``old_idx`` valid only where ``hit``.
    Canonical graphs are pair-sorted (``preprocess`` sorts by pair id),
    so the join is usually a direct searchsorted with no argsort."""
    pid_old = _canonical_pairs(old.src, old.dst, old.num_vertices)
    pid_new = _canonical_pairs(new.src, new.dst, old.num_vertices)
    if pid_old.size == 0:
        return (np.zeros(pid_new.size, dtype=bool),
                np.zeros(pid_new.size, dtype=np.int64))
    if bool(np.all(pid_old[1:] > pid_old[:-1])):
        order = None
        sorted_pid = pid_old
    else:
        order = np.argsort(pid_old, kind="stable")
        sorted_pid = pid_old[order]
    pos = np.searchsorted(sorted_pid, pid_new)
    pos_c = np.minimum(pos, sorted_pid.size - 1)
    hit = (pos < sorted_pid.size) & (sorted_pid[pos_c] == pid_new)
    return hit, (pos_c if order is None else order[pos_c])


def _anchor_tree_mask(old: IncrementalForest, new: Graph) -> np.ndarray:
    """F0 membership over the NEW graph's canonical edges: old tree pairs
    that survive with their weight unchanged (re-weighted pairs re-enter
    as probe candidates — their old certificates are void)."""
    if old.graph.num_edges == 0:
        return np.zeros(new.num_edges, dtype=bool)
    hit, old_idx = _match_pairs(old.graph, new)
    return hit & old.forest.edge_mask[old_idx] \
        & (new.weight == old.graph.weight[old_idx])


@functools.lru_cache(maxsize=None)
def _build_update_fns(num_vertices: int, mesh: Optional[Mesh],
                      collective: str = "pmin",
                      cand_cap: Optional[int] = None):
    """Compiled (labels, probe) pair of the incremental pass.

    ``labels`` runs the warm-started threshold-level chain of the filter
    (level j's ``connected_labels`` inits from level j-1 — only newly
    activated tree edges pay hook iterations) and finishes with
    :func:`~repro.kernels.spmv_minplus.ops.component_maxkey` warm-started
    from the TOP level, whose threshold is the max tree key — so the
    max-key loop converges without iterating and only pays the packed
    scatter-max.  ``probe`` evaluates every candidate edge against all
    three certificates (level connectivity below key, component max-key
    bound, top-level component crossing) in one launch; under a mesh the
    tree arrays and probe edges run sharded with labels replicated, as in
    the filter pass.
    """
    n = num_vertices
    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1

    def labels_fn(t_src, t_dst, t_key, thresholds, axis_name=None):
        comp, rows = None, []
        for j in range(thresholds.shape[0]):
            comp = minplus_ops.connected_labels(
                t_src, t_dst, t_key <= thresholds[j], num_vertices=n,
                init=comp, axis_name=axis_name,
                collective=collective, cand_cap=cand_cap,
                num_shards=num_shards)
            rows.append(comp)
        comp, maxkey = minplus_ops.component_maxkey(
            t_src, t_dst, t_key, t_key != keys_lib.INF_KEY,
            num_vertices=n, init=comp, axis_name=axis_name,
            collective=collective, cand_cap=cand_cap, num_shards=num_shards)
        return jnp.stack(rows), comp, maxkey

    def probe_fn(labels, comp, maxkey, thresholds, src, dst, key, tree):
        idx = jnp.searchsorted(thresholds, key, side="left")
        lvl = jnp.maximum(idx - 1, 0).astype(jnp.int64)
        u = jnp.clip(src, 0, n - 1).astype(jnp.int64)
        v = jnp.clip(dst, 0, n - 1).astype(jnp.int64)
        flat = labels.reshape(-1)
        below = (idx > 0) & (flat[lvl * n + u] == flat[lvl * n + v])
        joined = comp[u] == comp[v]
        over = joined & (key > maxkey[u])
        keep = tree | ~(below | over)
        cross = ~tree & ~joined & (key != keys_lib.INF_KEY)
        return keep, cross

    if mesh is not None:
        labels_fn = compat.shard_map(
            functools.partial(labels_fn, axis_name="x"), mesh,
            in_specs=(P("x"), P("x"), P("x"), P()),
            out_specs=(P(), P(), P()))
        probe_fn = compat.shard_map(
            probe_fn, mesh,
            in_specs=(P(), P(), P(), P(), P("x"), P("x"), P("x"), P("x")),
            out_specs=(P("x"), P("x")))
    return jax.jit(labels_fn), jax.jit(probe_fn)


def _pad_to(arrs, cap: int, fills):
    return tuple(
        np.concatenate([a, np.full(cap - a.size, f, a.dtype)])
        for a, f in zip(arrs, fills))


def _probe_candidates(g: Graph, tmask: np.ndarray, params: GHSParams,
                      mesh: Optional[Mesh]) -> "tuple[np.ndarray, int]":
    """(keep mask, cut-probe candidate count) over ``g``'s edges — the
    device half of the pass; ONE fused mask readback."""
    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    n = g.num_vertices
    tree_pos = np.flatnonzero(tmask)
    key = g.packed_keys

    levels = int(params.update_levels) or int(params.filter_levels)
    thresholds = _thresholds(key[tree_pos], levels)

    t_block = partition_lib.pow2ceil(
        max(-(-max(tree_pos.size, 8) // num_shards), 1))
    t_cap = t_block * num_shards
    t_src, t_dst = _pad_to((g.src[tree_pos], g.dst[tree_pos]), t_cap,
                           (PAD_VERTEX, PAD_VERTEX))
    (t_key,) = _pad_to((key[tree_pos],), t_cap, (keys_lib.INF_KEY,))

    # Compressed hook-min exchange, gated exactly as in the filter pass
    # (DESIGN.md §11): engage when the wire model beats the dense pmin.
    collective = runtime.resolve_collective(params.collective)
    cand_cap = None
    if num_shards > 1 and collective == "compressed":
        cap = max(partition_lib.pow2ceil(min(n, 2 * t_block)), 8)
        if (collectives.compressed_bytes(cap, num_shards, 4)
                < collectives.dense_bytes(n, num_shards, 4)):
            cand_cap = cap

    m_cap = partition_lib.pow2ceil(max(g.num_edges, 8, num_shards))
    p_src, p_dst = _pad_to((g.src, g.dst), m_cap, (PAD_VERTEX, PAD_VERTEX))
    (p_key,) = _pad_to((key,), m_cap, (keys_lib.INF_KEY,))
    (p_tree,) = _pad_to((tmask,), m_cap, (False,))

    labels_fn, probe_fn = _build_update_fns(
        n, mesh,
        "compressed" if cand_cap is not None else "pmin", cand_cap)
    with jax.enable_x64():
        labels, comp, maxkey = labels_fn(
            jnp.asarray(t_src), jnp.asarray(t_dst), jnp.asarray(t_key),
            jnp.asarray(thresholds))
        keep_d, cross_d = probe_fn(
            labels, comp, maxkey, jnp.asarray(thresholds),
            jnp.asarray(p_src), jnp.asarray(p_dst), jnp.asarray(p_key),
            jnp.asarray(p_tree))
        keep, cross = jax.device_get((keep_d, cross_d))
    keep = np.asarray(keep, dtype=bool)[:g.num_edges]
    probes = int(np.asarray(cross, dtype=bool)[:g.num_edges].sum())
    return keep, probes


def plan_updates(
    state: IncrementalForest,
    batch: EdgeBatch,
    params: GHSParams = DEFAULT_PARAMS,
    mesh: Optional[Mesh] = None,
    updated: Optional[Graph] = None,
) -> UpdatePlan:
    """Merge + probe: everything in :func:`apply_updates` up to (not
    including) the final candidate solve.  ``updated`` optionally passes a
    precomputed :func:`apply_edge_batch` result (the serving layer merges
    at admission to route the bucket, then plans at flush)."""
    g2 = apply_edge_batch(state.graph, batch) if updated is None else updated
    stats = IncrementalStats()

    # Structural changes actually applied: pairs that vanished, appeared,
    # or changed weight (pairs are unique per graph, so the join counts
    # are exact).
    hit, old_idx = _match_pairs(state.graph, g2)
    removed = state.graph.num_edges - int(hit.sum())
    added = int((~hit).sum())
    if state.graph.num_edges == 0:
        changed = 0
        tmask = np.zeros(g2.num_edges, dtype=bool)
    else:
        same_w = g2.weight == state.graph.weight[old_idx]
        changed = int((hit & ~same_w).sum())
        # F0 (anchor) membership reuses the same join — see
        # _anchor_tree_mask for the standalone form.
        tmask = hit & state.forest.edge_mask[old_idx] & same_w
    stats.updates_applied = removed + added + changed
    if tmask.any():
        keep, probes = _probe_candidates(g2, tmask, params, mesh)
        stats.host_syncs += 1     # fused keep/cross-mask fetch
        stats.extra_syncs += 1
        stats.replacement_probes = probes
    else:
        # No anchor forest (empty or fully invalidated tree): no
        # certificates exist, the final solve sees every edge.
        keep = np.ones(g2.num_edges, dtype=bool)

    stats.edges_filtered = int(g2.num_edges - keep.sum())
    stats.filter_passes = 1
    sub, index = partition_lib.subgraph_by_mask(g2, keep)
    stats.candidate_count = sub.num_edges
    return UpdatePlan(graph=g2, sub=sub, index=index, stats=stats)


def finalize_plan(plan: UpdatePlan,
                  sub_forest: ForestResult) -> IncrementalForest:
    """Lift the candidate forest back to the updated graph's canonical
    edges (inverse of the §10 subset renumbering) — the new handle."""
    g2 = plan.graph
    mask = partition_lib.lift_mask(plan.index, sub_forest.edge_mask,
                                   g2.num_edges)
    forest = runtime.forest_from_mask(
        g2, mask, num_components=sub_forest.num_components)
    forest.check_consistent(g2.num_vertices)
    return IncrementalForest(graph=g2, forest=forest)


def apply_updates(
    state: IncrementalForest,
    batch: EdgeBatch,
    params: GHSParams = DEFAULT_PARAMS,
    mesh: Optional[Mesh] = None,
    max_rounds: Optional[int] = None,
) -> "tuple[IncrementalForest, IncrementalStats]":
    """Apply one insert/delete batch to a solved forest.

    Returns ``(new_state, stats)`` with ``new_state.forest`` bit-identical
    to a from-scratch solve of ``apply_edge_batch(state.graph, batch)``
    under any engine/params/mesh — the candidate set provably contains the
    updated MSF and the final solve is exact under the global packed-key
    order (module docstring).  ``stats`` carries the update ledger
    (``updates_applied``, ``replacement_probes``, ``candidate_count``)
    plus the final solve's counters via ``merge``.
    """
    plan = plan_updates(state, batch, params=params, mesh=mesh)
    res, st = boruvka_dist.minimum_spanning_forest(
        plan.sub, params=params, mesh=mesh, max_rounds=max_rounds)
    plan.stats.merge(st)
    return finalize_plan(plan, res), plan.stats

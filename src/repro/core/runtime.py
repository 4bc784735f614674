"""Shared engine runtime — the interval-driven driver both MST engines use.

DESIGN.md §6.  Both engines (the paper-faithful message GHS and the
synchronous Borůvka reformulation) follow the same execution shape once
their inner loops are device-resident:

    compile a fused *interval* function   (lax.while_loop over N steps)
    loop:  dispatch one interval          (state stays on device)
           read back ONE fused scalar vector
           host decides: done? error? re-bucket/compact?

This module owns the pieces that are engine-independent:

* :func:`interval_loop` — the host driver harness.  Per interval it performs
  exactly one blocking device→host transfer (``jax.device_get`` on the
  dispatch's scalar outputs) and one ``host_syncs``/``intervals`` ledger
  update, then hands the scalars to an engine-specific ``finish`` hook that
  interprets them (raise on error flags, count rounds/supersteps, trigger
  compaction) and decides termination.  With ``overlap=True`` (DESIGN.md
  §11) the loop is double-buffered: interval k+1 is dispatched *before*
  interval k's scalar readback is consumed, so the blocking transfer hides
  behind in-flight device work — see the function docstring for the
  contract a pipelined engine must satisfy.
* :class:`EngineStats` — the unified stats protocol: every engine's stats
  object derives from it so benchmarks can meter host syncs and interval
  counts uniformly.
* :func:`forest_from_mask` — the shared forest-extraction path from a
  canonical edge bitmap to a :class:`ForestResult`.
* :func:`resolve_round_loop` — validation of the ``params.round_loop`` knob
  shared by both engines (``"device"`` fused loop / ``"host"`` legacy).
* :func:`prepare_edges` / :func:`vertex_partitioned` — the partition layer
  (DESIGN.md §7): both engines receive their input through these, so the
  ``params.partitioner`` knob and the device pipeline's no-host-round-trip
  hand-off live in ONE place.  ``prepare_edges`` accepts a host
  :class:`Graph` *or* a device-resident
  :class:`repro.core.pipeline.DeviceEdges` and returns an
  :class:`EdgeBundle` in engine layout; ``vertex_partitioned`` realizes a
  vertex partition for the block-routed GHS engine as a relabeling that
  preserves canonical edge ids (forests stay bit-identical).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import partition as partition_lib
from repro.core.graph import PAD_VERTEX, Graph
from repro.core.kruskal_ref import ForestResult

ROUND_LOOPS = ("device", "host")
ROUND_KERNELS = ("xla", "pallas")
INTERVAL_PIPELINES = (0, 1)


@dataclasses.dataclass
class EngineStats:
    """Host↔device traffic ledger common to every engine driver.

    ``host_syncs`` counts blocking transfer points: :func:`interval_loop`
    adds exactly one per consumed interval readback, and engine hooks add
    one for every blocking transfer they perform OUTSIDE the interval
    readback — mirrored into ``extra_syncs`` at the same site.  The
    pipeline-invariant contract, asserted by the cross-engine contract
    test, is therefore

        ``host_syncs == intervals + extra_syncs``

    with the engine-specific ``extra_syncs`` values:

    * single-graph device loops (Borůvka, GHS) — 1, the final state fetch,
      so ``host_syncs == intervals + 1`` for THOSE engines only;
    * the batched driver (DESIGN.md §8) — one final mask fetch per bucket,
      so ``extra_syncs == buckets``;
    * the filter hybrid (DESIGN.md §10) — the sub-solves' final fetches
      plus one keep-mask fetch per filter pass, summed through
      ``BatchStats.merge``;
    * legacy host loops — per-round winner/label readbacks and compaction
      re-uploads, one ``extra_syncs`` each.

    ``intervals`` counts driver dispatches — for a device-resident loop
    that is one per ``check_frequency`` steps; for a legacy host loop it
    equals the number of rounds/supersteps.

    ``edge_staging`` records which :func:`prepare_edges` path staged the
    engine's input: ``"device"`` (the DeviceEdges no-host-round-trip
    hand-off) or ``"host"`` (layout built host-side and uploaded).  Empty
    for engines that do not route through ``prepare_edges``.

    ``rounds_per_graph`` is filled by batched drivers (DESIGN.md §8): one
    round/superstep count per input graph, in input order.  Single-graph
    engines leave it empty.

    ``edges_filtered`` / ``filter_passes`` are filled by the Filter-Borůvka
    sampling hybrid (DESIGN.md §10): edges proven non-MSF by the cycle-rule
    connectivity probe and the number of sample→solve→filter passes run.
    Engines without a filter stage leave them 0.

    ``updates_applied`` / ``replacement_probes`` are filled by the
    incremental pass (DESIGN.md §13): structural edge changes actually
    applied by an :func:`repro.core.incremental.apply_updates` batch, and
    the cut-probe candidates — non-tree edges crossing components severed
    by tree-edge deletions, the pool the final solve elects replacement
    edges from.  Solve-from-scratch engines leave them 0.

    Overlap-aware accounting (DESIGN.md §11): ``host_syncs`` and
    ``intervals`` always count CONSUMED readbacks/dispatches, so the
    contract above is pipeline-invariant.  ``overlapped_syncs`` counts the
    readbacks that were consumed while a successor interval was already in
    flight (0 on a sequential loop); ``speculative_intervals`` counts
    trailing dispatches whose scalars were never fetched because
    termination had already been observed (their device work is a provable
    no-op — see interval_loop).  ``comm_bytes`` is the per-shard on-wire
    byte total of the engine's cross-shard reductions under the selected
    ``params.collective`` (0 off-mesh).
    """

    host_syncs: int = 0
    intervals: int = 0
    extra_syncs: int = 0
    edge_staging: str = ""
    rounds_per_graph: tuple = ()
    edges_filtered: int = 0
    filter_passes: int = 0
    updates_applied: int = 0
    replacement_probes: int = 0
    overlapped_syncs: int = 0
    speculative_intervals: int = 0
    comm_bytes: int = 0


def interval_loop(
    state: Any,
    dispatch: Callable[[Any], Tuple[Any, Any]],
    finish: Callable[[Any, Any], Tuple[Any, bool]],
    *,
    stats: EngineStats,
    max_intervals: int,
    fail_msg: str,
    overlap: bool = False,
) -> Any:
    """Drive a device-resident engine to completion.

    ``dispatch(state) -> (state, scalars)`` runs one fused interval on
    device and returns the new state plus the interval's scalar summary
    (any pytree of device scalars — fetched with ONE ``device_get``).
    ``finish(state, host_scalars) -> (state, done)`` interprets the fetched
    values: it raises on error flags, updates engine counters, may mutate
    the state (e.g. compaction re-dispatch), and reports termination.

    The contract is batch-rank-polymorphic: a dispatch may advance a whole
    graph bucket (state with a leading batch axis), in which case per-graph
    done flags must be reduced ON DEVICE to one scalar before they reach
    the summary — the driver still performs exactly one readback per
    interval regardless of batch size (DESIGN.md §8).

    ``overlap=True`` double-buffers the loop (DESIGN.md §11): interval
    k+1 is dispatched from interval k's device state BEFORE k's scalar
    readback is consumed, so the blocking transfer overlaps in-flight
    device work instead of draining the pipeline.  ``finish`` then runs
    one interval "late": it receives interval k's scalars but the state
    AFTER interval k+1.  A pipelined engine must therefore guarantee
    (1) an interval dispatched from a terminated state is a device no-op
    (state fixed point), so the speculative trailing interval cannot
    perturb the result, and (2) any state surgery ``finish`` performs from
    k's scalars (compaction caps, collective caps) stays correct against
    state k+1 — monotone-shrinking censuses give this for free.  Engines
    whose ``finish`` consumes per-interval state it would otherwise lose
    (the legacy host loops' winner bitmaps) must stay sequential.

    Each dispatch, readback and ``finish`` call runs inside a profiler span
    (``interval.dispatch``, ``interval.readback``, ``interval.finish``), so
    a trace splits the host's time per interval between enqueueing work,
    waiting for the device and the engine's own decisions.

    Raises ``RuntimeError(fail_msg)`` if ``max_intervals`` elapse without
    ``finish`` signalling done.
    """
    if not overlap:
        for _ in range(max_intervals):
            with TraceAnnotation("interval.dispatch"):
                state, scalars = dispatch(state)
            with TraceAnnotation("interval.readback"):
                vals = jax.device_get(scalars)  # the interval's one host sync
            stats.host_syncs += 1
            stats.intervals += 1
            with TraceAnnotation("interval.finish"):
                state, done = finish(state, vals)
            if done:
                return state
        raise RuntimeError(fail_msg)

    # One-interval-deep pipeline: `pending` is interval k's un-consumed
    # scalar summary while `state` already holds interval k's output.
    with TraceAnnotation("interval.dispatch"):
        state, pending = dispatch(state)
    for _ in range(max_intervals):
        with TraceAnnotation("interval.dispatch"):
            state, scalars = dispatch(state)     # interval k+1, speculative
        with TraceAnnotation("interval.readback"):
            vals = jax.device_get(pending)       # interval k's one host sync
        stats.host_syncs += 1
        stats.intervals += 1
        stats.overlapped_syncs += 1
        with TraceAnnotation("interval.finish"):
            state, done = finish(state, vals)
        if done:
            # Interval k terminated, so the in-flight k+1 ran on a fixed
            # point: its state is byte-identical and its scalars are never
            # fetched — no extra host sync.
            stats.speculative_intervals += 1
            return state
        pending = scalars
    raise RuntimeError(fail_msg)


def forest_from_mask(
    graph: Graph,
    mask: np.ndarray,
    *,
    num_components: Optional[int] = None,
) -> ForestResult:
    """Build a :class:`ForestResult` from a canonical edge bitmap.

    ``num_components`` defaults to ``num_vertices - num_tree_edges`` (exact
    for any forest); engines that track fragment labels may pass the label
    census instead.
    """
    mask = np.asarray(mask, dtype=bool)
    ntree = int(mask.sum())
    total = float(graph.weight[mask].sum(dtype=np.float64))
    if num_components is None:
        num_components = graph.num_vertices - ntree
    return ForestResult(
        total_weight=total,
        edge_mask=mask,
        num_components=num_components,
        num_tree_edges=ntree,
    )


def resolve_round_loop(round_loop: str) -> str:
    """Validate the shared ``params.round_loop`` knob."""
    if round_loop not in ROUND_LOOPS:
        raise ValueError(
            f"unknown round_loop {round_loop!r}; options: {ROUND_LOOPS}")
    return round_loop


def resolve_round_kernel(round_kernel: str) -> str:
    """Validate the ``params.round_kernel`` knob (Borůvka round body).

    ``"xla"`` — the per-edge scatter/gather chain (``_one_round``), the
    seed behavior.  ``"pallas"`` — the fused masked min-plus formulation
    backed by the ``kernels/spmv_minplus`` family (DESIGN.md §9); the
    device round loop and the batched path honor it, the legacy host loop
    and the faithful GHS engine ignore it.
    """
    if round_kernel not in ROUND_KERNELS:
        raise ValueError(
            f"unknown round_kernel {round_kernel!r}; options: {ROUND_KERNELS}")
    return round_kernel


def resolve_collective(collective: str) -> str:
    """Validate the ``params.collective`` knob (DESIGN.md §11): ``"pmin"``
    full-width reductions / ``"compressed"`` delta-exchange candidate
    lists (:func:`repro.sharding.collectives.pmin_compressed`)."""
    from repro.sharding import collectives
    return collectives.resolve_collective(collective)


def resolve_interval_pipeline(depth: int) -> int:
    """Validate the ``params.interval_pipeline`` knob: 0 = sequential
    dispatch→readback→decide, 1 = double-buffered intervals."""
    if depth not in INTERVAL_PIPELINES:
        raise ValueError(
            f"interval_pipeline must be one of {INTERVAL_PIPELINES}, "
            f"got {depth!r}")
    return depth


# ---------------------------------------------------------------------------
# Partition layer (DESIGN.md §7) — both engines' single entry for edges
# ---------------------------------------------------------------------------

def as_graph(source) -> Graph:
    """Host :class:`Graph` view of an engine input (Graph or DeviceEdges)."""
    if isinstance(source, Graph):
        return source
    return source.to_graph()


@dataclasses.dataclass
class EdgeBundle:
    """Edge state in engine layout, ready for the round loop.

    ``src``/``dst``/``key`` are device arrays of ``layout.num_slots`` slots
    (edge-sharded under a mesh); ``slot`` carries each slot's own index
    within its shard so tree-edge recording stays a local scatter under ANY
    partition, surviving on-device compaction (the winner bitmap keeps the
    load-time slot layout for the whole run).  ``source`` retains the
    caller's input for lazy host mirroring (ForestResult construction).
    """

    layout: partition_lib.EdgeLayout
    src: Any
    dst: Any
    key: Any
    slot: Any
    num_vertices: int
    num_edges: int
    source: Any
    staging: str = "host"       # which prepare_edges path staged the input:
                                # "device" — DeviceEdges handed over in place
                                # "host"   — host layout built + uploaded

    def graph(self) -> Graph:
        return as_graph(self.source)


def prepare_edges(
    source, partitioner_name: str, mesh, *, chunk: int
) -> EdgeBundle:
    """Stage edges on device under the chosen partitioner.

    * host :class:`Graph` — the partitioner's :class:`EdgeLayout` is built
      host-side, arrays are gathered into slot order and uploaded once.
    * :class:`~repro.core.pipeline.DeviceEdges` + ``block`` partitioner —
      the pipeline's canonical buffers ARE the block layout: they are handed
      to the engine as-is, no edge ever crossing back to host.  (Non-block
      partitioners fall back to the host mirror: their layouts are host
      decisions by design.)

    The taken path is recorded in ``EdgeBundle.staging`` (drivers surface
    it as ``EngineStats.edge_staging``), and a DeviceEdges input that
    CANNOT take the fast path — non-block partitioner, or a capacity not
    divisible by the engine's shard count — emits a ``UserWarning`` naming
    the reason, instead of silently mirroring the edges through host
    memory.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import keys as keys_lib
    from repro.core import pipeline as pipeline_lib

    part = partition_lib.get_partitioner(partitioner_name)
    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    edge_sh = NamedSharding(mesh, P("x")) if mesh is not None else None

    def put(a):
        import jax.numpy as jnp
        return (jax.device_put(a, edge_sh) if edge_sh is not None
                else jnp.asarray(a))

    staging = "host"
    if (isinstance(source, pipeline_lib.DeviceEdges)
            and part.name == "block"
            and source.capacity % num_shards == 0):
        staging = "device"
        cap = source.capacity
        block = cap // num_shards
        eid = np.arange(cap, dtype=np.int64)
        eid[source.num_edges:] = -1
        layout = partition_lib.EdgeLayout(num_shards=num_shards,
                                          block=block, eid=eid)
        # device_put re-lays-out to the engine mesh if the pipeline was
        # built on a different one; a no-op placement otherwise.
        src_d, dst_d, key_d = (put(source.src), put(source.dst),
                               put(source.key))
        n, m = source.num_vertices, source.num_edges
    else:
        if isinstance(source, pipeline_lib.DeviceEdges):
            why = (f"partitioner {part.name!r} is a host-side layout "
                   f"decision" if part.name != "block" else
                   f"capacity {source.capacity} is not divisible by "
                   f"num_shards {num_shards}")
            warnings.warn(
                f"DeviceEdges cannot take the no-host-round-trip fast "
                f"path ({why}); falling back to a full host mirror",
                stacklevel=2)
        graph = as_graph(source)
        layout = partition_lib.build_edge_layout(
            graph, part, num_shards, chunk)
        valid = layout.eid >= 0
        gather = layout.eid[valid]
        src_p = np.full(layout.num_slots, PAD_VERTEX, np.int32)
        dst_p = np.full(layout.num_slots, PAD_VERTEX, np.int32)
        key_p = np.full(layout.num_slots, keys_lib.INF_KEY, np.uint64)
        src_p[valid] = graph.src[gather]
        dst_p[valid] = graph.dst[gather]
        key_p[valid] = graph.packed_keys[gather]
        src_d, dst_d, key_d = put(src_p), put(dst_p), put(key_p)
        n, m = graph.num_vertices, graph.num_edges

    slot_np = (np.arange(layout.num_slots, dtype=np.int64)
               % layout.block).astype(np.int32)
    return EdgeBundle(layout=layout, src=src_d, dst=dst_d, key=key_d,
                      slot=put(slot_np), num_vertices=n, num_edges=m,
                      source=source, staging=staging)


def vertex_partitioned(graph: Graph, partitioner_name: str,
                       num_shards: int) -> Graph:
    """Realize a vertex partition for the block-routed GHS engine.

    Returns a relabeled graph whose block distribution equals the
    partitioner's assignment.  Edge order, weights, and canonical edge ids
    are untouched, so the engine's forest (recorded by canonical id) is
    bit-identical to running on the original labels.
    """
    part = partition_lib.get_partitioner(partitioner_name)
    if part.name == "block":
        return graph
    perm = part.vertex_perm(graph, num_shards)
    return partition_lib.relabel_graph(graph, perm)

"""Device-resident synchronous Borůvka/GHS engine (beyond-paper, DESIGN §3-4).

Re-formulates GHS for SPMD hardware: per round, every fragment's minimum
outgoing edge (MOE) is ONE segmented min over packed 64-bit keys
``(weight_bits << 32) | edge_id`` — GHS's ``Test``/``Report`` message waves
collapse into one segmented min (two native uint32 scatter-min passes over
the key's weight and edge-id lanes) and a single fused ``pmin`` collective
(the two-phase weight + tie-break election of earlier versions, with a
collective per phase, is gone; the packed key resolves both in the same
reduction).  Fragment merging
is min-hooking + pointer doubling (the ``Connect``/``Initiate`` waves).  The
paper's point-to-point short-message traffic — which it identifies as its
limiting factor (§4.2) — is off the critical path entirely.

The round loop itself is device-resident: a ``jax.lax.while_loop`` advances
up to ``check_frequency`` rounds per dispatch, accumulating tree edges into
an on-device ``edge_mask`` and testing termination on device, so the host
synchronizes ONCE per compaction interval instead of once (or more) per
round.  Edge compaction is an on-device prefix-sum stream compaction into
power-of-two buckets; edges never round-trip through host memory.

Edges are block-distributed across devices (shard_map over axis ``"x"``);
the fragment-label array ``comp`` is replicated (paper layout: vertices are
block-distributed, but labels are small — int32 per vertex).  The legacy
host-driven loop is retained as ``params.round_loop == "host"`` for the
before/after measurement in ``benchmarks/bench_round_loop.py`` and as an
ablation baseline; both loops are bit-identical to the Kruskal oracle.

For serving many graphs, :func:`minimum_spanning_forests` runs the SAME
round body over a leading batch axis (``jax.vmap`` over shape-bucketed,
padded lanes — DESIGN.md §8): one dispatch and one scalar readback per
interval for a whole bucket, per-interval Borůvka contraction (sort-based
fragment-pair dedup, provably election-invariant), and per-lane forests
bit-identical to the corresponding single-graph solves.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import keys as keys_lib
from repro.core import partition as partition_lib
from repro.core import runtime
from repro.core import union_find
from repro.core.graph import PAD_VERTEX, Graph
from repro.core.kruskal_ref import ForestResult
from repro.core.params import DEFAULT_PARAMS, GHSParams
from repro.sharding import collectives

INF32 = np.uint32(0xFFFFFFFF)
INF_KEY = keys_lib.INF_KEY
_AXIS = "x"


def _pad_pow2(arrs, multiple: int, fill_vals):
    """Pad to the next power-of-two multiple of ``multiple``.

    src/dst are filled with PAD_VERTEX (far out of vertex range — clamped
    gathers make padding edges self-loops by construction, see graph.py),
    keys/weights with their INF sentinel.
    """
    m = arrs[0].shape[0]
    target = multiple
    while target < m:
        target *= 2
    pad = target - m
    return [
        np.concatenate([a, np.full(pad, f, a.dtype)]) if pad else a
        for a, f in zip(arrs, fill_vals)
    ]


# Power-of-two bucket sizing shared with the layout builder.
_pow2ceil = partition_lib.pow2ceil


def _make_pmin(axis_name: Optional[str], collective: str,
               cand_cap: Optional[int], num_shards: int) -> Callable:
    """``pmin(x, default)`` closure for the round bodies.

    Identity off-mesh; full-width ``lax.pmin`` for ``collective="pmin"``
    (or when no candidate cap is in effect); the compressed delta exchange
    (:func:`repro.sharding.collectives.pmin_compressed`, DESIGN.md §11)
    otherwise.  ``default`` is the per-index baseline a shard contributes
    when its local edges did not improve the entry (``INF_KEY`` for MOE
    keys, the identity parent for hook requests) — the compressed path
    ships only the ``x != default`` entries.
    """
    if axis_name is None:
        return lambda x, default=None: x
    if collective != "compressed" or cand_cap is None:
        return lambda x, default=None: collectives.pmin(x, axis_name)

    def pmin(x, default):
        return collectives.pmin_compressed(
            x, axis_name, default=default, cap=cand_cap,
            num_shards=num_shards)

    return pmin


@dataclasses.dataclass
class BoruvkaStats(runtime.EngineStats):
    # host_syncs / intervals inherited from the runtime protocol; for the
    # legacy host loop, intervals == rounds (one dispatch per round).
    rounds: int = 0
    compactions: int = 0
    edges_scanned: int = 0          # Σ active (padded) edges per round
    doubling_steps: int = 0         # Σ pointer-doubling steps run per
                                    # round (a bucket counts its slowest
                                    # lane: the steps the vmapped loop ran)
    comm_history: tuple = ()        # device loop: one (mode, cand_cap,
                                    # rounds, bytes) record per consumed
                                    # interval — per-shard on-wire bytes of
                                    # the round collectives under the
                                    # DESIGN.md §11 wire model (mode is the
                                    # executable actually dispatched:
                                    # 'pmin' or 'compressed')


# ---------------------------------------------------------------------------
# Fused device-resident loop (round_loop="device", the default)
# ---------------------------------------------------------------------------

def _one_round(
    comp: jnp.ndarray,
    mask: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    key: jnp.ndarray,
    slot: jnp.ndarray,
    *,
    pmin: Callable,
    use_pallas: bool,
):
    """One Borůvka round: fused MOE election, winner recording, merging.

    Rank-polymorphic by construction: the single-graph interval calls it on
    (n,)/(cap,) state and the batched engine maps it over a leading batch
    axis with ``jax.vmap`` — both run the exact same ops, which is what
    keeps batched lanes bit-identical to single-graph solves.

    The phases run under the ``jax.named_scope`` names ``election``,
    ``record`` and ``merge``, which a profile's device ops carry in their
    op name (the interval's censuses and compaction add ``census`` and
    ``compact``).  Returns the new state, the done flag and the number of
    pointer-doubling steps the merge ran.
    """
    from repro.kernels.segment_min import ops as segops
    n = comp.shape[0]
    cap = mask.shape[0]
    with jax.named_scope("election"):
        cs = comp[src]      # PAD_VERTEX clamps → padding is a self-loop
        cd = comp[dst]
        alive = (cs != cd) & (key != INF_KEY)
        k = jnp.where(alive, key, INF_KEY)
        # Fused MOE election: ONE segmented min over both endpoints, ONE
        # collective.  The packed key carries the tie-break, so no second
        # pmin; locally the min runs as two uint32 scatter-min passes over
        # the key's lanes (the chip has no 64-bit scatter).
        seg = jnp.concatenate([cs, cd]).astype(jnp.int32)
        best = segops.segment_min64(
            jnp.concatenate([k, k]), seg, num_segments=n,
            use_pallas=use_pallas)
        best = pmin(best, INF_KEY)
        winners = alive & ((best[cs] == k) | (best[cd] == k))
        done = jnp.all(best == INF_KEY)
    with jax.named_scope("record"):
        # Record wins into the sharded bitmap; an edge's bitmap slot lives
        # on the shard that loaded it (compaction is shard-local), so the
        # scatter is local for every partitioner.
        mask = mask.at[jnp.where(winners, slot, cap)].set(True, mode="drop")
    with jax.named_scope("merge"):
        # Min-hooking + pointer doubling (GHS Connect/Initiate).
        hi = jnp.maximum(cs, cd).astype(jnp.uint32)
        lo = jnp.minimum(cs, cd).astype(jnp.uint32)
        parent = union_find.hook_min(n, hi, lo, winners)
        parent = pmin(parent, jnp.arange(n, dtype=jnp.uint32))
        parent, steps = union_find.pointer_double(parent)
        comp = parent[comp]
    return comp, mask, done, steps


def _censuses(comp, src, dst, key, axis_name: Optional[str],
              collective: str):
    """(max local active edges, max local candidate fragments) after an
    interval.

    The active-edge count picks the host's compaction bucket.  The
    candidate census caps the compressed collective: distinct fragments
    touched by local active edges bound every entry a shard can improve in
    ANY later round (fragments only merge and edges only die, so the count
    is non-increasing — valid even consumed one interval late under the
    double-buffered driver).  It costs a sort over both endpoint lanes, so
    it is taken only where a compressed exchange can read it (a mesh under
    ``collective="compressed"``); elsewhere the trivial bound ``n`` stands.
    """
    with jax.named_scope("census"):
        active = (comp[src] != comp[dst]) & (key != INF_KEY)
        n_active = active.sum(dtype=jnp.int32)
        n = comp.shape[0]
        if not axis_name:
            return n_active, jnp.int32(n)
        if collective == "compressed":
            seg = jnp.concatenate([comp[src], comp[dst]]).astype(jnp.uint32)
            seg = jnp.where(jnp.concatenate([active, active]), seg,
                            jnp.uint32(n))
            (seg,) = jax.lax.sort((seg,), num_keys=1)
            first = (seg != jnp.uint32(n)) & jnp.concatenate(
                [jnp.ones((1,), bool), seg[1:] != seg[:-1]])
            n_cand = jax.lax.pmax(first.sum(dtype=jnp.int32), axis_name)
        else:
            n_cand = jnp.int32(n)
        return jax.lax.pmax(n_active, axis_name), n_cand


def _run_interval(
    comp: jnp.ndarray,
    mask: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    key: jnp.ndarray,
    slot: jnp.ndarray,
    rounds: jnp.ndarray,
    *,
    axis_name: Optional[str],
    use_pallas: bool,
    collective: str = "pmin",
    cand_cap: Optional[int] = None,
    num_shards: int = 1,
):
    """Advance up to ``rounds`` Borůvka rounds entirely on device.

    State per shard: replicated fragment labels ``comp``, the per-slot tree
    bitmap ``mask`` (frozen in the load-time layout of the partitioner —
    slot i on shard s is canonical edge ``layout.eid[s*block + i]``), and
    the (possibly compacted) local edge arrays.  Each edge carries its own
    load-time ``slot`` index, so winner recording is a local scatter under
    ANY partition and survives compaction.  Returns the new state plus a
    replicated (done, rounds-run, doubling steps, max local active count,
    max local candidate count) vector — the ONLY values the host ever
    reads.

    ``collective``/``cand_cap`` pick the cross-shard reduction (DESIGN.md
    §11): full-width ``lax.pmin`` or the compressed delta exchange with a
    static per-shard candidate cap (the host re-caps per interval from the
    candidate census below).
    """
    pmin = _make_pmin(axis_name, collective, cand_cap, num_shards)

    def one_round(comp, mask):
        return _one_round(comp, mask, src, dst, key, slot,
                          pmin=pmin, use_pallas=use_pallas)

    def cond(c):
        r, _, _, done, _ = c
        return jnp.logical_not(done) & (r < rounds)

    def body(c):
        r, comp, mask, _, dbl = c
        comp, mask, done, steps = one_round(comp, mask)
        return r + 1, comp, mask, done, dbl + steps

    r, comp, mask, done, dbl = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), comp, mask, jnp.bool_(False), jnp.int32(0)))
    return (comp, mask, done, r, dbl,
            *_censuses(comp, src, dst, key, axis_name, collective))


def _one_round_fused(
    comp: jnp.ndarray,
    mask: jnp.ndarray,      # (m,) canonical-eid bitmap, replicated
    src: jnp.ndarray,
    dst: jnp.ndarray,
    key: jnp.ndarray,
    csrc: jnp.ndarray,      # (m,) canonical endpoints, replicated
    cdst: jnp.ndarray,
    *,
    pmin: Callable,
    lowering: str,
    sort_bits,
):
    """One Borůvka round as the fused masked min-plus SpMV (DESIGN.md §9).

    ``params.round_kernel == "pallas"``: the election is ONE
    ``spmv_minplus.elect`` call (masked min-plus SpMV — Pallas kernel,
    scatter-free sort lowering, or the scatter oracle, chosen statically),
    and everything after it runs at fragment scale ``n`` instead of edge
    scale ``cap``:

    * the elected ``best[f]`` already NAMES the winning edge (the packed
      key's id lane is the canonical edge id), so winner recording is an
      n-scale scatter into a replicated canonical-eid bitmap — the
      cap-scale ``winners`` recompute + slot scatter of :func:`_one_round`
      disappears, and so does the end-of-solve slot→canonical remap;
    * the merge partner is recovered from the replicated canonical
      endpoint arrays (two n-scale gathers), so hooking is n-scale too;
    * ``best`` is already globally reduced, so the hook requests are
      identical on every shard and :func:`_one_round`'s second collective
      (the parent ``pmin``) is dropped — ONE collective per round;
    * shortcut + relabel are :func:`_one_round`'s: pointer doubling to
      its fixpoint, then ``parent[comp]``.

    Election over identical packed keys, identical winner set, identical
    hook pairs (each elected fragment contributes the same (hi, lo) its
    winning edge would), identical pointer doubling — bit-identical to
    :func:`_one_round` by construction, which the adversarial corpus and
    the bench sweep both enforce.
    """
    from repro.kernels.spmv_minplus import ops as spmv_ops
    n = comp.shape[0]
    m = mask.shape[0]
    with jax.named_scope("election"):
        cs = comp[src]      # PAD_VERTEX clamps → padding is a self-loop
        cd = comp[dst]
        best = spmv_ops.elect(cs, cd, key, num_segments=n,
                              lowering=lowering, sort_bits=sort_bits)
        best = pmin(best, INF_KEY)
        elected = best != INF_KEY
        eid = keys_lib.unpack_edge_id(best)  # 0xFFFFFFFF when not elected
        done = jnp.all(best == INF_KEY)
    with jax.named_scope("record"):
        mask = mask.at[jnp.where(elected, eid, jnp.uint32(m))].set(
            True, mode="drop")
    with jax.named_scope("merge"):
        u = csrc[eid]       # clamped gathers; garbage gated by ``elected``
        v = cdst[eid]
        cu = comp[u]
        cv = comp[v]
        f = jnp.arange(n, dtype=jnp.uint32)
        other = jnp.where(cu == f, cv, cu)
        hi = jnp.maximum(f, other)
        lo = jnp.minimum(f, other)
        parent = union_find.hook_min(n, hi, lo, elected)
        parent, steps = union_find.pointer_double(parent)
        comp = parent[comp]
    return comp, mask, done, steps


def _run_interval_fused(
    comp: jnp.ndarray,
    mask: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    key: jnp.ndarray,
    csrc: jnp.ndarray,
    cdst: jnp.ndarray,
    rounds: jnp.ndarray,
    *,
    axis_name: Optional[str],
    lowering: str,
    sort_bits,
    collective: str = "pmin",
    cand_cap: Optional[int] = None,
    num_shards: int = 1,
):
    """:func:`_run_interval` with the fused round body (round_kernel="pallas").

    Differences from the XLA interval: the tree bitmap is canonical-eid
    indexed and REPLICATED (every shard derives the same writes from the
    globally-reduced election, so no slot side-lane and no final remap),
    and the per-edge ``slot`` array is not consumed — compaction still
    threads it through the engine state for shape uniformity.  The round's
    single collective routes through the same ``collective``/``cand_cap``
    selection as the XLA interval (hooking needs the globally-reduced
    ``best``, and the compressed exchange returns exactly that).
    """
    pmin = _make_pmin(axis_name, collective, cand_cap, num_shards)

    def one_round(comp, mask):
        return _one_round_fused(comp, mask, src, dst, key, csrc, cdst,
                                pmin=pmin, lowering=lowering,
                                sort_bits=sort_bits)

    def cond(c):
        r, _, _, done, _ = c
        return jnp.logical_not(done) & (r < rounds)

    def body(c):
        r, comp, mask, _, dbl = c
        comp, mask, done, steps = one_round(comp, mask)
        return r + 1, comp, mask, done, dbl + steps

    r, comp, mask, done, dbl = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), comp, mask, jnp.bool_(False), jnp.int32(0)))
    return (comp, mask, done, r, dbl,
            *_censuses(comp, src, dst, key, axis_name, collective))


@functools.lru_cache(maxsize=64)
def _build_interval_fn_fused(
        mesh: Optional[Mesh], lowering: str, sort_bits,
        collective: str = "pmin",
        cand_cap: Optional[int] = None) -> Callable:
    # cand_cap is part of the cache key: compressed caps are power-of-two
    # and shrink monotonically with the census, so at most log2(n) variants
    # compile per solve (same budget as the compaction buckets).
    donate = (0, 1)
    if mesh is None:
        fn = partial(_run_interval_fused, axis_name=None, lowering=lowering,
                     sort_bits=sort_bits)
        return jax.jit(fn, donate_argnums=donate)
    num_shards = int(np.prod(mesh.devices.shape))
    fn = compat.shard_map(
        partial(_run_interval_fused, axis_name=_AXIS, lowering=lowering,
                sort_bits=sort_bits, collective=collective,
                cand_cap=cand_cap, num_shards=num_shards),
        mesh,
        # mask + canonical endpoints replicated (see _run_interval_fused);
        # only the edge working set is sharded.
        in_specs=(P(), P(), P(_AXIS), P(_AXIS), P(_AXIS), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P(), P()),
    )
    return jax.jit(fn, donate_argnums=donate)


_PAD_SLOT = np.int32(0x7FFF0000)   # out of any mask range → scatter-dropped


def _compact_shard(comp, src, dst, key, slot, *, cap: int):
    """Prefix-sum stream compaction of the local edge block to ``cap`` slots.

    Runs entirely on device — dead edges (endpoints in the same fragment)
    are dropped, survivors slide to the front (carrying their load-time
    bitmap ``slot``), the tail refills with the inert padding sentinels.
    ``cap`` is static (a power-of-two bucket), so shapes stay rectangular
    across shards.
    """
    with jax.named_scope("compact"):
        keep = (comp[src] != comp[dst]) & (key != INF_KEY)
        pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
        idx = jnp.where(keep, pos, cap)
        new_src = jnp.full((cap,), PAD_VERTEX, jnp.int32).at[idx].set(
            src, mode="drop")
        new_dst = jnp.full((cap,), PAD_VERTEX, jnp.int32).at[idx].set(
            dst, mode="drop")
        new_key = jnp.full((cap,), INF_KEY, jnp.uint64).at[idx].set(
            key, mode="drop")
        new_slot = jnp.full((cap,), _PAD_SLOT, jnp.int32).at[idx].set(
            slot, mode="drop")
    return new_src, new_dst, new_key, new_slot


@functools.lru_cache(maxsize=64)
def _build_interval_fn(mesh: Optional[Mesh], use_pallas: bool,
                       collective: str = "pmin",
                       cand_cap: Optional[int] = None) -> Callable:
    # rounds is a traced scalar, so one executable serves every interval
    # length and graph size per (mesh, shapes).  comp/mask are the mutated
    # state — donate so device buffers are reused in place.  cand_cap is
    # static (see _build_interval_fn_fused for the recompile budget).
    donate = (0, 1)
    if mesh is None:
        fn = partial(_run_interval, axis_name=None, use_pallas=use_pallas)
        return jax.jit(fn, donate_argnums=donate)
    num_shards = int(np.prod(mesh.devices.shape))
    fn = compat.shard_map(
        partial(_run_interval, axis_name=_AXIS, use_pallas=use_pallas,
                collective=collective, cand_cap=cand_cap,
                num_shards=num_shards),
        mesh,
        in_specs=(P(), P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS),
                  P()),
        out_specs=(P(), P(_AXIS), P(), P(), P(), P(), P()),
    )
    return jax.jit(fn, donate_argnums=donate)


@functools.lru_cache(maxsize=64)
def _build_compact_fn(mesh: Optional[Mesh], cap: int) -> Callable:
    # No donation here: compaction is shrink-only, so the inputs are strictly
    # larger than the outputs and could never alias them anyway.
    if mesh is None:
        return jax.jit(partial(_compact_shard, cap=cap))
    fn = compat.shard_map(
        partial(_compact_shard, cap=cap), mesh,
        in_specs=(P(), P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS)),
        out_specs=(P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS)),
    )
    return jax.jit(fn)


def _device_engine(
    source,
    params: GHSParams,
    mesh: Optional[Mesh],
    max_rounds: Optional[int],
) -> tuple[ForestResult, BoruvkaStats]:
    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    chunk = max(8 * num_shards, num_shards)

    if isinstance(source, Graph):
        # Host-built weights may be arbitrary; the pipeline's are (0, 1) by
        # construction, so only the host path needs the sentinel check.
        if np.any(source.weight.view(np.uint32) == INF32):
            raise ValueError("weights collide with the INF sentinel")

    collective = runtime.resolve_collective(params.collective)
    fused = runtime.resolve_round_kernel(params.round_kernel) == "pallas"
    edge_sh = NamedSharding(mesh, P(_AXIS)) if mesh is not None else None
    repl_sh = NamedSharding(mesh, P()) if mesh is not None else None

    def put(a, sh):
        return jax.device_put(a, sh) if sh is not None else jnp.asarray(a)

    with jax.enable_x64():
        with TraceAnnotation("msf.stage"):
            bundle = runtime.prepare_edges(
                source, params.partitioner, mesh, chunk=chunk)
            n, m = bundle.num_vertices, bundle.num_edges
            comp_dev = put(np.arange(n, dtype=np.uint32), repl_sh)
            if fused:
                # Fused round body (DESIGN.md §9): replicated canonical-eid
                # bitmap + replicated canonical endpoints for n-scale
                # winner recording and hooking.  bundle.graph() is the
                # same host mirror forest_from_mask reads at the end, so
                # this stages no transfer the solve would not have made
                # anyway.
                g_host = bundle.graph()
                pad = np.zeros(1, np.int32)
                csrc_d = put(g_host.src if m else pad, repl_sh)
                cdst_d = put(g_host.dst if m else pad, repl_sh)
                mask_dev = put(np.zeros(m, dtype=bool), repl_sh)
            else:
                mask_dev = put(np.zeros(bundle.layout.num_slots, dtype=bool),
                               edge_sh)
        layout = bundle.layout
        src_d, dst_d, key_d, slot_d = (bundle.src, bundle.dst, bundle.key,
                                       bundle.slot)

        # fn_pmin: full-width pmin rounds.  Under collective="compressed"
        # it is the dense fallback, and still takes the candidate census
        # the compressed executables' caps are derived from.  Off a mesh
        # there is no collective, so one executable (keyed "pmin") serves
        # both settings.
        fn_coll = collective if mesh is not None else "pmin"
        if fused:
            from repro.kernels.spmv_minplus import ops as spmv_ops
            sort_bits = spmv_ops.sort_gate(n, m)
            if sort_bits is not None and np.any(
                    g_host.weight.view(np.uint32)
                    >= spmv_ops.WEIGHT_LIMIT_BITS):
                sort_bits = None   # host weights outside (0, 1): no sort key
            lowering = ("pallas" if params.use_pallas
                        else "sort" if sort_bits is not None else "scatter")
            sb = sort_bits if lowering == "sort" else None
            fn_pmin = _build_interval_fn_fused(mesh, lowering, sb, fn_coll)
        else:
            lowering = sb = None
            fn_pmin = _build_interval_fn(mesh, params.use_pallas, fn_coll)

        overlap = (runtime.resolve_interval_pipeline(
            params.interval_pipeline) == 1)
        interval = max(params.check_frequency, 1)
        cap_rounds = max_rounds or (n + 2)
        stats = BoruvkaStats()
        stats.edge_staging = bundle.staging
        comm_hist = []
        # Value lanes of the per-round reductions, for the §11 wire model:
        # xla rounds exchange best (uint64) + hook parents (uint32); fused
        # rounds have ONE collective, best only.
        value_bytes = (8,) if fused else (8, 4)
        # cand_bound: upper bound on any shard's per-round candidate count
        # for the NEXT dispatch — refreshed from the interval's
        # distinct-touched-fragments census, which is non-increasing
        # across rounds, so it stays a valid bound even when finish runs
        # one interval late (overlap).  Pre-census bound: each local edge
        # touches at most two fragments.
        box = dict(cur_block=layout.block, dispatched=0, inflight=[],
                   cand_bound=min(n, 2 * layout.block))

        def pick_fn():
            """Select the next dispatch's interval executable + §11 byte
            model: the compressed delta exchange with the census-derived
            candidate cap when its wire model beats full-width pmin, the
            dense pmin executable otherwise (bit-identical either way)."""
            full_b = sum(collectives.dense_bytes(n, num_shards, vb)
                         for vb in value_bytes)
            if num_shards > 1 and collective == "compressed":
                cand_cap = max(_pow2ceil(box["cand_bound"]), 8)
                comp_b = sum(
                    collectives.compressed_bytes(cand_cap, num_shards, vb)
                    for vb in value_bytes)
                if comp_b < full_b:
                    f = (_build_interval_fn_fused(
                            mesh, lowering, sb, "compressed", cand_cap)
                         if fused else
                         _build_interval_fn(mesh, params.use_pallas,
                                            "compressed", cand_cap))
                    return f, "compressed", cand_cap, comp_b
            return fn_pmin, "pmin", 0, full_b

        def dispatch(s):
            comp_dev, mask_dev, src_d, dst_d, key_d, slot_d = s
            # Clamp by the DISPATCHED total, not stats.rounds: under
            # overlap a dispatch happens before the previous interval's
            # readback is consumed.
            this_rounds = max(min(interval, cap_rounds - box["dispatched"]),
                              0)
            f, mode, cand_cap, bytes_per_round = pick_fn()
            if fused:
                comp_dev, mask_dev, *summary = f(
                    comp_dev, mask_dev, src_d, dst_d, key_d, csrc_d, cdst_d,
                    this_rounds)
            else:
                comp_dev, mask_dev, *summary = f(
                    comp_dev, mask_dev, src_d, dst_d, key_d, slot_d,
                    this_rounds)
            box["dispatched"] += this_rounds
            # FIFO of per-dispatch ledger records; finish pops the OLDEST
            # (it may run one interval late under overlap) and scales by
            # the rounds the interval actually ran.
            box["inflight"].append(
                (mode, cand_cap, box["cur_block"], bytes_per_round))
            # The interval's scalar summary: five replicated values
            # (done, rounds, doubling steps, censuses), fetched by the
            # runtime with ONE device_get.
            return (comp_dev, mask_dev, src_d, dst_d, key_d, slot_d), \
                tuple(summary)

        def finish(s, vals):
            done_v, r, dbl, n_act, n_cand = vals
            mode, cand_cap, blk, bytes_per_round = box["inflight"].pop(0)
            r = int(r)
            stats.rounds += r
            stats.doubling_steps += int(dbl)
            stats.edges_scanned += r * blk * num_shards
            stats.comm_bytes += r * bytes_per_round
            comm_hist.append((mode, cand_cap, r, r * bytes_per_round))
            box["cand_bound"] = max(min(n, int(n_cand)), 1)
            if bool(done_v):
                return s, True
            if params.compaction == "pow2":
                new_block = max(_pow2ceil(int(n_act)), 8)
                if new_block < box["cur_block"]:   # shrink: ≤ log2 recompiles
                    comp_dev, mask_dev, src_d, dst_d, key_d, slot_d = s
                    with TraceAnnotation("msf.compact"):
                        cfn = _build_compact_fn(mesh, new_block)
                        src_d, dst_d, key_d, slot_d = cfn(
                            comp_dev, src_d, dst_d, key_d, slot_d)
                    s = (comp_dev, mask_dev, src_d, dst_d, key_d, slot_d)
                    box["cur_block"] = new_block
                    stats.compactions += 1
            return s, False

        comp_dev, mask_dev = runtime.interval_loop(
            (comp_dev, mask_dev, src_d, dst_d, key_d, slot_d), dispatch,
            finish, stats=stats, max_intervals=cap_rounds,
            fail_msg="Borůvka engine failed to converge",
            overlap=overlap)[:2]

        with TraceAnnotation("msf.fetch"):
            comp_final, mask_full = jax.device_get((comp_dev, mask_dev))
        stats.host_syncs += 1          # final state fetch
        stats.extra_syncs += 1

    with TraceAnnotation("msf.forest"):
        comp_final = np.asarray(comp_final)
        if fused:
            # The fused rounds record winners canonical-eid-indexed directly.
            mask = np.asarray(mask_full)
        else:
            # The bitmap lives in the load-time slot layout; the layout maps
            # slots back to canonical edge ids (padding slots never set).
            mask = layout.canonical_mask(np.asarray(mask_full), m)
        ncomp = int(np.unique(comp_final).size)
        res = runtime.forest_from_mask(bundle.graph(), mask,
                                       num_components=ncomp)
        res.check_consistent(n)
    stats.comm_history = tuple(comm_hist)
    return res, stats


# ---------------------------------------------------------------------------
# Batched multi-graph engine (DESIGN.md §8): the same round loop under vmap
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchStats(BoruvkaStats):
    """Stats for a batched solve.  ``rounds_per_graph`` (inherited from the
    runtime protocol) is ordered like the input sequence; ``bucket_shapes``
    records one ``(n_pad, cap, batch_size)`` triple per dispatched bucket.

    :meth:`merge` is also the accumulation base of
    :class:`repro.core.filter_boruvka.FilterStats` — the filter driver sums
    its sample/final sub-solves' ledgers through the same path."""

    buckets: int = 0
    bucket_shapes: tuple = ()

    def merge(self, st: BoruvkaStats) -> None:
        """Accumulate a sub-solve's ledger (one bucket, or one single-graph
        fallback run) — the ONE place the shared counters are summed."""
        self.host_syncs += st.host_syncs
        self.intervals += st.intervals
        self.extra_syncs += st.extra_syncs
        self.rounds += st.rounds
        self.compactions += st.compactions
        self.edges_scanned += st.edges_scanned
        self.doubling_steps += st.doubling_steps
        self.overlapped_syncs += st.overlapped_syncs
        self.speculative_intervals += st.speculative_intervals
        self.comm_bytes += st.comm_bytes
        self.comm_history += st.comm_history


def _one_round_packed(comp, mask, src, dst, key, slot, *,
                      s_bits: int, c_bits: int, election: str = "scatter"):
    """One Borůvka round specialized to the batched identity layout.

    Bit-identical to :func:`_one_round` (same elections, same winner set,
    same merges) but cheaper on scatter-bound backends: the election value
    packs (weight-bits ‖ edge-id ‖ other-endpoint-fragment) into one uint64
    — appending the other fragment BELOW the unique edge id cannot change
    the (weight, id) total order — so the elected ``best[f]`` already names
    the winning edge's bitmap slot (slot == canonical id in this layout)
    AND the fragment to merge with.  Winner recording and min-hooking then
    scatter ``n_pad`` per-fragment requests instead of ``cap`` per-edge
    ones; the only cap-scale scatter left is the election itself.
    """
    n = comp.shape[0]
    cap = mask.shape[0]
    ones = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    with jax.named_scope("election"):
        cs = comp[src]      # PAD_VERTEX clamps → padding is a self-loop
        cd = comp[dst]
        alive = (cs != cd) & (key != INF_KEY)
        wbits = key >> jnp.uint64(32)
        eid = key & jnp.uint64(0xFFFFFFFF)
        base = ((wbits << jnp.uint64(c_bits + s_bits))
                | (eid << jnp.uint64(s_bits)))
        if election == "sort":
            # round_kernel="pallas", batched: the same masked min-plus
            # election lowered scatter-free — prepend the electing fragment
            # ABOVE the packed value (weights < 1.0 keep wbits in 30 bits,
            # so (seg ‖ wbits ‖ eid ‖ other) is 2·s_bits + 30 + c_bits ≤ 64,
            # the contraction gate), key-only sort, and read each
            # fragment's winner back with a searchsorted probe.  Exact min
            # over identical values → bit-identical to the scatter
            # election.
            shift = jnp.uint64(30 + c_bits + s_bits)
            sk = jnp.concatenate([
                jnp.where(alive, (cs.astype(jnp.uint64) << shift)
                          | base | cd.astype(jnp.uint64), ones),
                jnp.where(alive, (cd.astype(jnp.uint64) << shift)
                          | base | cs.astype(jnp.uint64), ones),
            ])
            (sk,) = jax.lax.sort((sk,), num_keys=1)
            m2 = sk.shape[0]
            frag = jnp.arange(n, dtype=jnp.uint64)
            pos = jnp.searchsorted(sk, frag << shift)
            cand = sk[jnp.minimum(pos, m2 - 1)]
            found = (pos < m2) & ((cand >> shift) == frag) & (cand != ones)
            low = (jnp.uint64(1) << shift) - jnp.uint64(1)
            best = jnp.where(found, cand & low, ones)
        else:
            seg = jnp.concatenate([cs, cd]).astype(jnp.int32)
            val = jnp.concatenate([
                jnp.where(alive, base | cd.astype(jnp.uint64), ones),
                jnp.where(alive, base | cs.astype(jnp.uint64), ones),
            ])
            best = jnp.full((n,), ones, jnp.uint64).at[seg].min(
                val, mode="drop")
        elected = best != ones
        best_eid = ((best >> jnp.uint64(s_bits))
                    & jnp.uint64((1 << c_bits) - 1)).astype(jnp.int32)
        other = (best & jnp.uint64((1 << s_bits) - 1)).astype(jnp.uint32)
        done = jnp.all(best == ones)
    with jax.named_scope("record"):
        mask = mask.at[jnp.where(elected, best_eid, cap)].set(
            True, mode="drop")
    with jax.named_scope("merge"):
        f = jnp.arange(n, dtype=jnp.uint32)
        hi = jnp.maximum(f, other)
        lo = jnp.minimum(f, other)
        parent = union_find.hook_min(n, hi, lo, elected)
        parent, steps = union_find.pointer_double(parent)
        comp = parent[comp]
    return comp, mask, done, steps


def _contract_lane(comp, src, dst, key, *, s_bits: int, c_bits: int):
    """Borůvka contraction of one batch lane — sort-based, scatter-free.

    Endpoints are rewritten to their fragment labels and parallel
    cross-fragment edges collapse to the min-key edge per fragment pair.
    This cannot change any future election: a dropped edge shares its
    fragment pair with a strictly smaller key, so it can never be ANY
    fragment's minimum outgoing edge (and ``done`` still flips exactly when
    no fragment has an outgoing edge).  Forests stay bit-identical.

    The whole (lo-fragment, hi-fragment, weight-bits, edge-id) quadruple
    packs into ONE uint64 — fragment labels fit ``s_bits`` each, (0, 2)
    weights have zero sign/exponent-MSB so their IEEE bits fit 30, and in
    the batched identity layout the canonical edge id doubles as the bitmap
    slot and fits ``c_bits`` — so contraction is two *key-only* sorts (pair
    grouping, then survivors-to-front), the cheap primitive on XLA:CPU
    (DESIGN.md §7), instead of the per-element scatters that dominate the
    round loop at serving scales.  Every field unpacks from the sorted key.
    """
    with jax.named_scope("compact"):
        ones = jnp.uint64(0xFFFFFFFFFFFFFFFF)
        cu = comp[src]      # PAD_VERTEX clamps → padding stays a self-loop
        cd = comp[dst]
        alive = (cu != cd) & (key != INF_KEY)
        lo = jnp.minimum(cu, cd).astype(jnp.uint64)
        hi = jnp.maximum(cu, cd).astype(jnp.uint64)
        wbits = key >> jnp.uint64(32)
        eid = key & jnp.uint64(0xFFFFFFFF)
        packed = ((lo << jnp.uint64(c_bits + 30 + s_bits))
                  | (hi << jnp.uint64(c_bits + 30))
                  | (wbits << jnp.uint64(c_bits))
                  | eid)
        packed = jnp.where(alive, packed, ones)
        (packed,) = jax.lax.sort((packed,), num_keys=1)
        pair = packed >> jnp.uint64(c_bits + 30)
        valid = packed != ones
        first = valid & jnp.concatenate(
            [jnp.ones((1,), bool), pair[1:] != pair[:-1]])
        count = first.sum(dtype=jnp.int32)
        (kept,) = jax.lax.sort((jnp.where(first, packed, ones),), num_keys=1)
        dead = kept == ones
        eid2 = kept & jnp.uint64((1 << c_bits) - 1)
        wb2 = (kept >> jnp.uint64(c_bits)) & jnp.uint64((1 << 30) - 1)
        hi2 = ((kept >> jnp.uint64(c_bits + 30))
               & jnp.uint64((1 << s_bits) - 1))
        lo2 = kept >> jnp.uint64(c_bits + 30 + s_bits)
        new_src = jnp.where(dead, PAD_VERTEX, lo2.astype(jnp.int32))
        new_dst = jnp.where(dead, PAD_VERTEX, hi2.astype(jnp.int32))
        new_key = jnp.where(dead, INF_KEY, (wb2 << jnp.uint64(32)) | eid2)
        new_slot = jnp.where(dead, _PAD_SLOT, eid2.astype(jnp.int32))
    return new_src, new_dst, new_key, new_slot, count


def _run_interval_batch(
    comp: jnp.ndarray,      # (B, n_pad) uint32
    mask: jnp.ndarray,      # (B, cap) bool
    src: jnp.ndarray,       # (B, cap) int32
    dst: jnp.ndarray,
    key: jnp.ndarray,       # (B, cap) uint64
    slot: jnp.ndarray,      # (B, cap) int32
    done: jnp.ndarray,      # (B,) bool
    rdone: jnp.ndarray,     # (B,) int32 — per-graph rounds run so far
    rounds: jnp.ndarray,
    *,
    use_pallas: bool,
    contract_bits: Optional[Tuple[int, int]],
    election: str = "scatter",
):
    """Advance up to ``rounds`` Borůvka rounds for a whole graph bucket.

    Every round maps :func:`_one_round` over the leading batch axis; lanes
    whose graph has converged are frozen (their ``done`` flag gates the
    carry update), so each lane's comp/mask/rounds trajectory is exactly the
    single-graph engine's.  Termination for the host is the per-graph done
    vector reduced to ONE replicated scalar (``all_done``) — the interval's
    single readback, per the runtime contract.

    When ``contract_bits = (s_bits, c_bits)`` rounds run the packed-key
    variant (:func:`_one_round_packed`) and the interval ends with a fused
    per-lane :func:`_contract_lane` (in place, same capacity); the returned
    census is then the max DEDUPED edge count, so the host's next shrink
    needs no extra readback.  Without it, rounds are plain
    :func:`_one_round` and the census counts active slots (the fallback for
    buckets whose packing doesn't fit 64 bits).
    """
    if contract_bits is not None:
        s_bits, c_bits = contract_bits
        step = jax.vmap(partial(_one_round_packed, s_bits=s_bits,
                                c_bits=c_bits, election=election))
    else:
        step = jax.vmap(partial(_one_round, pmin=lambda x, default=None: x,
                                use_pallas=use_pallas))

    def cond(c):
        r, _, _, done, _, _ = c
        return jnp.logical_not(jnp.all(done)) & (r < rounds)

    def body(c):
        r, comp, mask, done, rdone, dbl = c
        comp2, mask2, done2, steps = step(comp, mask, src, dst, key, slot)
        live = jnp.logical_not(done)
        comp = jnp.where(live[:, None], comp2, comp)
        mask = jnp.where(live[:, None], mask2, mask)
        rdone = rdone + live.astype(jnp.int32)
        done = done | done2
        # The vmapped doubling ran until its slowest lane stopped.
        return r + 1, comp, mask, done, rdone, dbl + steps.max()

    r, comp, mask, done, rdone, dbl = jax.lax.while_loop(
        cond, body, (jnp.int32(0), comp, mask, done, rdone, jnp.int32(0)))

    if contract_bits is not None:
        s_bits, c_bits = contract_bits
        src, dst, key, slot, counts = jax.vmap(
            partial(_contract_lane, s_bits=s_bits, c_bits=c_bits))(
                comp, src, dst, key)
        census = counts.max()
    else:
        # Active-edge census (max over lanes) for the compaction cap.
        with jax.named_scope("census"):
            census = jax.vmap(
                lambda c, s, d, k: ((c[s] != c[d]) & (k != INF_KEY)).sum(
                    dtype=jnp.int32))(comp, src, dst, key).max()
    return (comp, mask, src, dst, key, slot, done, rdone,
            jnp.all(done), r, dbl, census)


@functools.lru_cache(maxsize=256)
def _build_batch_interval_fn(
        use_pallas: bool, contract_bits: Optional[Tuple[int, int]],
        election: str = "scatter") -> Callable:
    # The whole per-lane state is mutated (contraction rewrites the edge
    # arrays too) — donate it all for in-place reuse; rounds is traced, so
    # one executable serves every interval length per bucket shape.
    # contract_bits is per (n_pad, cap) bucket shape, so the cache must
    # hold the serving lattice's full combo set: evicting an entry
    # destroys the jit object AND every executable warmup compiled
    # through it, re-paying those compiles mid-request (a 256-vertex
    # lattice has ~60 combos — the old maxsize=16 silently discarded
    # most of the §12 warmup).
    donate = (0, 1, 2, 3, 4, 5, 6, 7)
    fn = partial(_run_interval_batch, use_pallas=use_pallas,
                 contract_bits=contract_bits, election=election)
    return jax.jit(fn, donate_argnums=donate)


@functools.lru_cache(maxsize=64)
def _build_batch_shrink_fn(cap: int) -> Callable:
    """Slice every lane's (contracted, front-packed) edge arrays down to
    ``cap`` slots — a static-shape copy, no readback needed."""
    def shrink(src, dst, key, slot):
        with jax.named_scope("compact"):
            return src[:, :cap], dst[:, :cap], key[:, :cap], slot[:, :cap]
    return jax.jit(shrink)


@functools.lru_cache(maxsize=64)
def _build_batch_compact_fn(cap: int) -> Callable:
    """Per-lane stream compaction of a bucket to ``cap`` slots (vmapped
    :func:`_compact_shard` — survivors keep their load-time ``slot``, so
    winner recording stays a local scatter under the batch axis too)."""
    return jax.jit(jax.vmap(partial(_compact_shard, cap=cap)))


def warm_bucket(
    batch_size: int,
    n_pad: int,
    cap: int,
    params: GHSParams = DEFAULT_PARAMS,
) -> int:
    """Precompile EVERY executable a ``(batch_size, n_pad, cap)`` bucket
    can touch during a solve (DESIGN.md §12 warmup): the vmapped interval
    fn at the load cap AND at every pow2 compaction cap below it, plus the
    shrink slices between those caps.

    Solving an all-ghost flush only compiles the load-cap trace — ghost
    lanes converge before ever compacting, so without this the FIRST real
    flush of a shape pays the post-shrink retraces mid-request, exactly
    the latency spike warmup exists to prevent.  Under a bounded lattice
    the widened uniform contraction bits (:func:`_lattice_contract_bits`)
    make every cap share one fn object, so a larger cap's ladder covers
    the smaller caps' load traces and re-warms are cache hits; without
    bounds the fn cache key carries the ORIGINAL bucket's bits and the
    sub-cap traces are per-cap.  Mirrors ``_solve_bucket``'s static-key
    computation on an empty batch: the contraction gate and election
    lowering are data-independent for (0, 1)-weight traffic.  Only the
    contracted
    front-packed shrink path is warmed (the plain per-lane compact path
    runs only when the bit-gate fails, which pipeline weights never
    trigger).  Returns the number of executables compiled."""
    B = int(batch_size)
    s_bits = max(n_pad - 1, 1).bit_length()
    c_bits = max(cap - 1, 1).bit_length()
    contract_bits = ((s_bits, c_bits)
                     if params.compaction == "pow2"
                     and 2 * s_bits + 30 + c_bits <= 64 else None)
    # Mirror _solve_bucket's widening so the fn object warmed here IS the
    # runtime fn object (and sub-cap traces are shared across caps).
    contract_bits = _widen_contract_bits(contract_bits, params)
    election = "scatter"
    if (runtime.resolve_round_kernel(params.round_kernel) == "pallas"
            and contract_bits is not None):
        election = "sort"
    fn = _build_batch_interval_fn(params.use_pallas, contract_bits,
                                  election)

    # The load cap itself plus every pow2 compaction target below it
    # (``finish`` only ever shrinks to ``max(pow2ceil(census), 8)``).
    # A run-to-completion interval (>= the n_pad + 2 round bound, the §12
    # dispatch policy) converges every lane inside the FIRST dispatch, so
    # the shrink ladder can never run — warming it would compile
    # executables the runtime cannot reach.
    caps = [cap]
    if params.batch_check_frequency < n_pad + 2:
        c = 8
        while c * 2 < cap:
            c *= 2
        while c >= 8 and c < cap:
            caps.append(c)
            c //= 2
    compiled = 0
    with jax.enable_x64():
        for cur in caps:
            # Fresh state every iteration: the interval fn donates all
            # eight state buffers, so nothing it consumed may be reused.
            comp = jnp.asarray(
                np.broadcast_to(np.arange(n_pad, dtype=np.uint32),
                                (B, n_pad)).copy())
            mask = jnp.zeros((B, cap), bool)
            done = jnp.zeros((B,), bool)
            rdone = jnp.zeros((B,), jnp.int32)
            src = jnp.full((B, cur), PAD_VERTEX, jnp.int32)
            dst = jnp.full((B, cur), PAD_VERTEX, jnp.int32)
            key = jnp.full((B, cur), INF_KEY, jnp.uint64)
            slot = jnp.asarray(partition_lib.batched_slots(B, cur))
            state = fn(comp, mask, src, dst, key, slot, done, rdone, 1)
            jax.block_until_ready(state)
            compiled += 1
            _, _, src_o, dst_o, key_o, slot_o, _, _ = state[:8]
            for new in caps:
                if new >= cur:
                    continue
                out = _build_batch_shrink_fn(new)(
                    src_o, dst_o, key_o, slot_o)
                jax.block_until_ready(out)
                compiled += 1
    return compiled


def _lattice_contract_bits(params: GHSParams) -> Optional[Tuple[int, int]]:
    """Uniform contraction bit-widths for a bounded serving lattice.

    When the params carry per-graph capacity bounds (the §12 service), every
    bucket's packed rounds can use the LATTICE TOP's (s_bits, c_bits)
    instead of its own: wider shift widths are sound (labels < n_pad ≤
    n_top, slot ids < cap ≤ cap_top, and the ≤ 64-bit gate is checked at
    the top), and uniform widths mean ONE jit fn object — hence one set of
    per-shape executables — serves every cap's compaction ladder.  Without
    this, each original cap keys its own fn object and the warmup lattice
    compiles O(shapes · ladder) distinct interval executables whose JIT
    code mappings can exhaust ``vm.max_map_count`` (observed: a
    256-vertex/1024-edge lattice × 4 flush widths ran the process out of
    mmaps mid-warmup)."""
    if (params.compaction != "pow2" or not params.batch_max_vertices
            or not params.batch_max_edges):
        return None
    n_top = _pow2ceil(int(params.batch_max_vertices))
    cap_top = _pow2ceil(max(int(params.batch_max_edges), 8))
    s_bits = max(n_top - 1, 1).bit_length()
    c_bits = max(cap_top - 1, 1).bit_length()
    if 2 * s_bits + 30 + c_bits > 64:
        return None
    return (s_bits, c_bits)


def _widen_contract_bits(
        contract_bits: Optional[Tuple[int, int]],
        params: GHSParams) -> Optional[Tuple[int, int]]:
    """Promote a bucket's own contraction bits to the lattice-top widths
    when the params define a lattice that covers them (see
    :func:`_lattice_contract_bits`)."""
    if contract_bits is None:
        return None
    lat = _lattice_contract_bits(params)
    if (lat is not None and lat[0] >= contract_bits[0]
            and lat[1] >= contract_bits[1]):
        return lat
    return contract_bits


def _contract_gate(batch) -> Optional[Tuple[int, int]]:
    """(s_bits, c_bits) when the bucket's contraction quadruple fits one
    uint64 — fragment labels need ``log2(n_pad)`` bits each, weight bits 30
    (requires every weight < 2.0, which the (0, 1) invariant gives; checked
    against the actual keys so arbitrary host graphs stay safe), and the
    canonical edge id ``log2(cap)``.  ``None`` falls back to plain
    compaction (bit-identical either way, just fewer sort savings)."""
    s_bits = max(batch.n_pad - 1, 1).bit_length()
    c_bits = max(batch.cap - 1, 1).bit_length()
    if 2 * s_bits + 30 + c_bits > 64:
        return None
    real = batch.key != INF_KEY
    if np.any(real & ((batch.key >> np.uint64(32)) >= np.uint64(1 << 30))):
        return None
    return (s_bits, c_bits)


def _solve_bucket(
    batch,                       # pipeline.GraphBatch
    params: GHSParams,
    max_rounds: Optional[int],
) -> tuple[list[ForestResult], BatchStats]:
    """Run one shape bucket through the vmapped device round loop."""
    n_pad, cap, B = batch.n_pad, batch.cap, batch.batch_size
    contract_bits = (_contract_gate(batch)
                     if params.compaction == "pow2" else None)
    contract_bits = _widen_contract_bits(contract_bits, params)
    # round_kernel="pallas" under vmap: the fused formulation IS the packed
    # round (n-scale recording + hooking); what changes is the election
    # lowering — scatter-free sort when the bucket passes the bit gate and
    # every weight sits below 1.0 (keeps the all-ones dead sentinel
    # unreachable).  Ungated buckets keep the plain XLA fallback rounds.
    election = "scatter"
    if (runtime.resolve_round_kernel(params.round_kernel) == "pallas"
            and contract_bits is not None):
        real = batch.key != keys_lib.INF_KEY
        wbits = batch.key >> np.uint64(32)
        if not np.any(real & (wbits >= np.uint64(0x3F800000))):
            election = "sort"

    with jax.enable_x64():
        with TraceAnnotation("msf.stage"):
            src_d = jnp.asarray(batch.src)
            dst_d = jnp.asarray(batch.dst)
            key_d = jnp.asarray(batch.key)
            slot_d = jnp.asarray(batch.slot)
            comp_dev = jnp.asarray(
                np.broadcast_to(np.arange(n_pad, dtype=np.uint32),
                                (B, n_pad)).copy())
            mask_dev = jnp.zeros((B, cap), bool)
            done_dev = jnp.zeros((B,), bool)
            rdone_dev = jnp.zeros((B,), jnp.int32)

        overlap = (runtime.resolve_interval_pipeline(
            params.interval_pipeline) == 1)
        interval = max(params.batch_check_frequency, 1)
        cap_rounds = max_rounds or (n_pad + 2)
        stats = BatchStats(buckets=1, bucket_shapes=((n_pad, cap, B),))
        box = dict(cur_cap=cap, dispatched=0, inflight=[])

        fn = _build_batch_interval_fn(params.use_pallas, contract_bits,
                                      election)

        def dispatch(s):
            comp, mask, src_d, dst_d, key_d, slot_d, done, rdone = s
            this_rounds = max(min(interval, cap_rounds - box["dispatched"]),
                              0)
            state = fn(comp, mask, src_d, dst_d, key_d, slot_d, done, rdone,
                       this_rounds)
            box["dispatched"] += this_rounds
            box["inflight"].append(box["cur_cap"])   # popped by finish (FIFO)
            # The interval's scalar summary: the per-graph done vector is
            # already reduced on device, so the host reads ONE flag per
            # interval no matter how many graphs ride the bucket.
            return state[:8], state[8:]

        def finish(s, vals):
            all_done, r, dbl, census = vals
            stats.rounds += int(r)
            stats.doubling_steps += int(dbl)
            stats.edges_scanned += int(r) * box["inflight"].pop(0) * B
            if bool(all_done):
                return s, True
            if params.compaction == "pow2":
                new_cap = max(_pow2ceil(int(census)), 8)
                if new_cap < box["cur_cap"]:   # shrink: ≤ log2 recompiles
                    comp, mask, src_d, dst_d, key_d, slot_d, done, rdone = s
                    with TraceAnnotation("msf.compact"):
                        if contract_bits is not None:
                            # Contraction already packed survivors to the
                            # front — shrinking is a static slice, no
                            # readback.
                            cfn = _build_batch_shrink_fn(new_cap)
                            src_d, dst_d, key_d, slot_d = cfn(
                                src_d, dst_d, key_d, slot_d)
                        else:
                            cfn = _build_batch_compact_fn(new_cap)
                            src_d, dst_d, key_d, slot_d = cfn(
                                comp, src_d, dst_d, key_d, slot_d)
                    s = (comp, mask, src_d, dst_d, key_d, slot_d, done,
                         rdone)
                    box["cur_cap"] = new_cap
                    stats.compactions += 1
            return s, False

        state = runtime.interval_loop(
            (comp_dev, mask_dev, src_d, dst_d, key_d, slot_d, done_dev,
             rdone_dev), dispatch, finish, stats=stats,
            max_intervals=cap_rounds,
            fail_msg="batched Borůvka engine failed to converge",
            overlap=overlap)
        mask_dev, rdone_dev = state[1], state[7]

        # The bucket's single final fetch: mask + per-graph round counts.
        with TraceAnnotation("msf.fetch"):
            mask_h, rdone_h = jax.device_get((mask_dev, rdone_dev))
        stats.host_syncs += 1          # the bucket's final fetch
        stats.extra_syncs += 1

    with TraceAnnotation("msf.forest"):
        results = batch.unpack(mask_h)
    stats.rounds_per_graph = tuple(int(x) for x in np.asarray(rdone_h))
    return results, stats


def solve_packed(
    batch,                       # pipeline.GraphBatch
    params: GHSParams = DEFAULT_PARAMS,
    max_rounds: Optional[int] = None,
) -> tuple[list[ForestResult], BatchStats]:
    """Solve ONE pre-packed shape bucket (DESIGN.md §12).

    The incremental counterpart of :func:`minimum_spanning_forests`: a
    serving loop that routed requests through
    :func:`repro.core.pipeline.bucket_shape` and packed a queue with
    :func:`repro.core.pipeline.pack_bucket` dispatches the bucket here
    without re-listing (or re-bucketing) the batch.  Results come back in
    lane order; each forest is bit-identical to the single-graph solve.
    Device loop only — the host fallback has no packed form.
    """
    if runtime.resolve_round_loop(params.round_loop) != "device":
        raise ValueError(
            "solve_packed requires round_loop='device'; the host loop "
            "solves graphs one at a time via minimum_spanning_forest")
    for r, g in enumerate(batch.graphs):
        if np.any(g.weight.view(np.uint32) == INF32):
            raise ValueError(
                f"lane {r}: weights collide with the INF sentinel")
    return _solve_bucket(batch, params, max_rounds)


def minimum_spanning_forests(
    graphs,
    params: GHSParams = DEFAULT_PARAMS,
    max_rounds: Optional[int] = None,
) -> tuple[list[ForestResult], BatchStats]:
    """Solve many graphs per dispatch (DESIGN.md §8).

    Graphs are bucketed by padded shape (:func:`repro.core.pipeline.
    pack_batch` under the ``params.batch_bucket`` policy) and each bucket
    runs the device round loop under ``jax.vmap`` — one dispatch and one
    scalar readback per interval for the WHOLE bucket, amortizing compile
    and dispatch cost across the batch.  Results come back in input order
    and every forest is bit-identical to the corresponding single-graph
    :func:`minimum_spanning_forest` solve (same ops per lane, same packed
    total order).

    ``params.round_loop == "host"`` falls back to a loop of single-graph
    solves (the bench baseline); the batched fast path is device-only.
    """
    from repro.core import pipeline as pipeline_lib

    graph_list = [runtime.as_graph(g) for g in graphs]
    for i, g in enumerate(graph_list):
        if np.any(g.weight.view(np.uint32) == INF32):
            raise ValueError(
                f"graph {i}: weights collide with the INF sentinel")

    # Bucket + validate FIRST: the batch_bucket policy and the
    # batch_max_vertices/batch_max_edges capacity guards must reject bad
    # inputs on every loop driver, not just the vmapped fast path.
    batches = pipeline_lib.pack_batch(
        graph_list, bucket=params.batch_bucket,
        max_vertices=params.batch_max_vertices or None,
        max_edges=params.batch_max_edges or None)

    if runtime.resolve_round_loop(params.round_loop) == "host":
        stats = BatchStats()
        results = []
        rounds = []
        for g in graph_list:
            res, st = _host_engine(g, params, None, max_rounds)
            results.append(res)
            rounds.append(st.rounds)
            stats.merge(st)
        stats.rounds_per_graph = tuple(rounds)
        return results, stats

    results: list = [None] * len(graph_list)
    rounds = [0] * len(graph_list)
    stats = BatchStats()
    shapes = []
    for batch in batches:
        bres, bst = _solve_bucket(batch, params, max_rounds)
        for idx, res, r in zip(batch.indices, bres, bst.rounds_per_graph):
            results[idx] = res
            rounds[idx] = r
        stats.merge(bst)
        shapes.extend(bst.bucket_shapes)
    stats.buckets = len(batches)
    stats.bucket_shapes = tuple(shapes)
    stats.rounds_per_graph = tuple(rounds)
    return results, stats


# ---------------------------------------------------------------------------
# Legacy host-driven loop (round_loop="host"): per-round syncs + host-side
# compaction.  Kept as the before/after baseline for bench_round_loop.py.
# ---------------------------------------------------------------------------

def _segmin_scatter(n, idx, val, order=None):
    """Per-segment min via XLA scatter-min (default path)."""
    return jnp.full((n,), INF32, jnp.uint32).at[idx].min(val)


def _segmin_pallas(n, idx, val, order=None):
    """Per-segment min via the Pallas sort+scan kernel (TPU hot-spot path;
    interpret-mode on CPU, validated bit-equal to the scatter path)."""
    from repro.kernels.segment_min import ops as segops
    return segops.segment_min(val, idx.astype(jnp.int32), num_segments=n,
                              use_pallas=True, order=order)


def _round_body(
    comp: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    wbits: jnp.ndarray,
    eid: jnp.ndarray,
    *,
    axis_name: Optional[str],
    use_pallas: bool = False,
):
    """One two-phase round: elect MOE per fragment, hook, compress, relabel."""
    n = comp.shape[0]
    pmin = (lambda x: jax.lax.pmin(x, axis_name)) if axis_name else (lambda x: x)
    segmin = _segmin_pallas if use_pallas else _segmin_scatter

    cs = comp[src]
    cd = comp[dst]
    alive = (cs != cd) & (wbits != INF32)
    wb = jnp.where(alive, wbits, INF32)

    # Sort once per endpoint array, reuse across both election phases.
    order_s = jnp.argsort(cs.astype(jnp.int32)) if use_pallas else None
    order_d = jnp.argsort(cd.astype(jnp.int32)) if use_pallas else None

    # Phase 1: best weight per fragment (local scatter-min, global pmin).
    bw = jnp.minimum(segmin(n, cs, wb, order_s), segmin(n, cd, wb, order_d))
    bw = pmin(bw)

    # Phase 2: tie-break by unique edge id among weight-matching edges.
    cand_s = jnp.where(alive & (wb == bw[cs]), eid, INF32)
    cand_d = jnp.where(alive & (wb == bw[cd]), eid, INF32)
    be = jnp.minimum(segmin(n, cs, cand_s, order_s),
                     segmin(n, cd, cand_d, order_d))
    be = pmin(be)

    # Winners: the elected MOE edges (each fragment elects exactly one).
    winners = alive & ((be[cs] == eid) | (be[cd] == eid))

    # Merge: min-hooking + pointer doubling (GHS Connect/Initiate collapse).
    hi = jnp.maximum(cs, cd).astype(jnp.uint32)
    lo = jnp.minimum(cs, cd).astype(jnp.uint32)
    parent = union_find.hook_min(n, hi, lo, winners)
    parent = pmin(parent)
    parent, steps = union_find.pointer_double(parent)
    new_comp = parent[comp]

    done = jnp.all(bw == INF32)
    return new_comp, winners, done, steps


def _make_round_fn(mesh: Optional[Mesh], use_pallas: bool = False) -> Callable:
    if mesh is None:
        return jax.jit(partial(_round_body, axis_name=None,
                               use_pallas=use_pallas))
    fn = compat.shard_map(
        partial(_round_body, axis_name=_AXIS, use_pallas=use_pallas),
        mesh,
        in_specs=(P(), P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS)),
        out_specs=(P(), P(_AXIS), P(), P()),
    )
    return jax.jit(fn)


def _host_engine(
    source,
    params: GHSParams,
    mesh: Optional[Mesh],
    max_rounds: Optional[int],
) -> tuple[ForestResult, BoruvkaStats]:
    graph = runtime.as_graph(source)
    n, m = graph.num_vertices, graph.num_edges
    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    chunk = max(8 * num_shards, num_shards)

    src = graph.src.astype(np.int32)
    dst = graph.dst.astype(np.int32)
    wbits = graph.weight.view(np.uint32).copy()
    eid = np.arange(m, dtype=np.uint32)
    if np.any(wbits == INF32):
        raise ValueError("weights collide with the INF sentinel")

    # The legacy loop tracks edges by canonical id end to end, so a
    # partitioner is simply the initial upload order here (compaction
    # re-block-distributes the survivors, as the seed driver always did).
    part = partition_lib.get_partitioner(params.partitioner)
    if part.name != "block" and m:
        order = np.concatenate([
            np.flatnonzero(part.edge_shard(graph, num_shards) == s)
            for s in range(num_shards)
        ]).astype(np.int64)
    else:
        order = np.arange(m, dtype=np.int64)

    round_fn = _make_round_fn(mesh, use_pallas=params.use_pallas)
    comp_sharding = (
        NamedSharding(mesh, P()) if mesh is not None else None
    )
    edge_sharding = (
        NamedSharding(mesh, P(_AXIS)) if mesh is not None else None
    )

    stats = BoruvkaStats()

    def put_edges(arrs):
        arrs = _pad_pow2(arrs, chunk, [PAD_VERTEX, PAD_VERTEX, INF32, INF32])
        stats.host_syncs += 1          # host→device re-upload
        stats.extra_syncs += 1
        if edge_sharding is not None:
            return [jax.device_put(a, edge_sharding) for a in arrs]
        return [jnp.asarray(a) for a in arrs]

    comp = np.arange(n, dtype=np.uint32)
    comp_dev = (
        jax.device_put(comp, comp_sharding) if comp_sharding is not None
        else jnp.asarray(comp)
    )
    src_d, dst_d, wb_d, eid_d = put_edges(
        [src[order], dst[order], wbits[order], eid[order]])

    mask = np.zeros(m, dtype=bool)
    cap = max_rounds or (n + 2)
    # Host mirror of the active edge set (for compaction + winner mapping).
    box = dict(active=order.copy())

    def dispatch(s):
        comp_dev, src_d, dst_d, wb_d, eid_d, _ = s
        comp_dev, winners, done, steps = round_fn(comp_dev, src_d, dst_d,
                                                  wb_d, eid_d)
        # The runtime fetches the done flag and the doubling steps (the
        # legacy loop's per-round sync); the winner bitmap readback below
        # is an extra, metered one.
        return (comp_dev, src_d, dst_d, wb_d, eid_d, winners), (done, steps)

    def finish(s, vals):
        done_v, steps = vals
        comp_dev, src_d, dst_d, wb_d, eid_d, winners = s
        rnd = stats.rounds
        stats.rounds += 1
        stats.doubling_steps += int(steps)
        stats.edges_scanned += int(src_d.shape[0])
        if bool(done_v):
            return s, True
        stats.host_syncs += 1          # device→host: winner bitmap + ids
        stats.extra_syncs += 1
        w = np.asarray(winners)
        if w.any():
            eids = np.asarray(eid_d)[w]
            mask[eids[eids != INF32].astype(np.int64)] = True
        # C1 analogue: lazy compaction every check_frequency rounds.
        if (
            params.compaction == "pow2"
            and (rnd + 1) % max(params.check_frequency, 1) == 0
        ):
            stats.host_syncs += 1      # device→host: fragment labels
            stats.extra_syncs += 1
            comp_h = np.asarray(comp_dev)
            active = box["active"]
            keep = comp_h[src[active]] != comp_h[dst[active]]
            if not keep.all():
                box["active"] = active = active[keep]
                stats.compactions += 1
                src_d, dst_d, wb_d, eid_d = put_edges(
                    [src[active], dst[active],
                     wbits[active], eid[active].astype(np.uint32)]
                )
                s = (comp_dev, src_d, dst_d, wb_d, eid_d, winners)
        return s, False

    comp_dev = runtime.interval_loop(
        (comp_dev, src_d, dst_d, wb_d, eid_d, None), dispatch, finish,
        stats=stats, max_intervals=cap,
        fail_msg="Borůvka engine failed to converge")[0]

    comp_final = np.asarray(comp_dev)
    ncomp = int(np.unique(comp_final).size)
    res = runtime.forest_from_mask(graph, mask, num_components=ncomp)
    res.check_consistent(n)
    return res, stats


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def minimum_spanning_forest(
    graph,
    params: GHSParams = DEFAULT_PARAMS,
    mesh: Optional[Mesh] = None,
    max_rounds: Optional[int] = None,
) -> tuple[ForestResult, BoruvkaStats]:
    """Run the optimized engine; returns the forest + execution stats.

    ``graph`` is a host :class:`Graph` or a device-resident
    :class:`repro.core.pipeline.DeviceEdges` — with the latter (and the
    default ``block`` partitioner) edges flow from the generation pipeline
    into the round loop without ever visiting host memory.

    ``params.round_loop`` selects the loop driver: ``"device"`` (default) is
    the fused host-sync-free ``lax.while_loop`` engine; ``"host"`` is the
    legacy per-round host loop.  ``params.partitioner`` picks the edge
    distribution (block / hashed / balanced — DESIGN.md §7).  All
    combinations produce bit-identical forests.

    This entry is also the sub-solver of the filter-Borůvka hybrid
    (:mod:`repro.core.filter_boruvka`, DESIGN.md §10): the sample and
    final solves are ordinary invocations over canonical-order subset
    graphs, so every knob above composes with ``method="filter_boruvka"``
    unchanged.
    """
    if runtime.resolve_round_loop(params.round_loop) == "host":
        return _host_engine(graph, params, mesh, max_rounds)
    return _device_engine(graph, params, mesh, max_rounds)

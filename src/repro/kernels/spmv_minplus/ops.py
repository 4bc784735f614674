"""jit'd dispatch for the fused Borůvka round body (DESIGN.md §9).

Three lowerings of the SAME masked min-plus election, selected statically:

* ``"scatter"`` — the XLA oracle (two scatter-mins); always available.
* ``"sort"``    — scatter-free: packs (fragment ‖ weight-bits ‖ edge-id)
  into one uint64, key-only sorts, and reads each fragment's winner with a
  ``searchsorted`` probe.  This is the fast lowering on backends where
  scatters serialize (XLA:CPU — see DESIGN.md §7/§9); gated by
  :func:`sort_gate` on the bit budget.
* ``"pallas"``  — the :mod:`.spmv_minplus` masked pair-lex scan kernel
  (sort by fragment, tiled masked segmented min-scan, conflict-free
  run-end extraction).  The accelerator lowering: compiled by Mosaic on
  the TPU, interpreted on the CPU so tests check the exact kernel
  semantics (:func:`repro.kernels.interpret_mode`).

All three are exact min-reductions over identical packed keys, so they are
bit-identical by construction — tests enforce it under hypothesis-generated
layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as keys_lib
from repro.core import union_find
from repro.kernels.spmv_minplus import ref
from repro.kernels.spmv_minplus.spmv_minplus import masked_minplus_scan

INF_U64 = keys_lib.INF_KEY
_PAD_SEG = np.int32(0x7FFFFFF0)
# Weight-bits budget of the sort lowering: engine weights lie in (0, 1), so
# their IEEE-754 patterns are < 0x3F800000 < 2**30 - 1 (keys.py contract).
WEIGHT_BITS = 30
WEIGHT_LIMIT_BITS = np.uint32(0x3F800000)  # ieee754_bits(1.0f)

ELECT_LOWERINGS = ("scatter", "sort", "pallas")


def sort_gate(num_vertices: int, num_edges: int) -> "tuple[int, int] | None":
    """(s_bits, c_bits) for the sort lowering, or None when fragment labels
    + 30-bit weights + edge ids cannot share one uint64 sort key.

    Callers must separately guarantee weight bits < 2**30 (true for the
    (0, 1) pipeline weights; host graphs are checked against
    ``WEIGHT_LIMIT_BITS``), which also keeps the all-ones dead sentinel
    unreachable by any live edge.
    """
    s_bits = max(int(num_vertices) - 1, 1).bit_length()
    c_bits = max(int(num_edges) - 1, 1).bit_length()
    if s_bits + WEIGHT_BITS + c_bits > 64:
        return None
    return s_bits, c_bits


def _elect_sort(cs, cd, key, *, num_segments, sort_bits):
    """Scatter-free election: key-only sort + searchsorted winner probe."""
    s_bits, c_bits = sort_bits
    shift = np.uint64(WEIGHT_BITS + c_bits)
    payload_mask = np.uint64((1 << (WEIGHT_BITS + c_bits)) - 1)
    eid_mask = np.uint64((1 << c_bits) - 1)
    ones = INF_U64

    alive = (cs != cd) & (key != INF_U64)
    # payload = (weight-bits ‖ edge-id), re-based from the 32-bit edge-id
    # lane of the engine key to the graph's actual c_bits width.
    payload = (((key >> np.uint64(32)) << np.uint64(c_bits))
               | (key & np.uint64(0xFFFFFFFF)))

    def side(seg):
        sk = (seg.astype(jnp.uint64) << shift) | payload
        return jnp.where(alive, sk, ones)

    (pk,) = jax.lax.sort((jnp.concatenate([side(cs), side(cd)]),),
                         num_keys=1)
    m2 = pk.shape[0]
    frag = jnp.arange(num_segments, dtype=jnp.uint64)
    pos = jnp.searchsorted(pk, frag << shift)
    cand = pk[jnp.minimum(pos, m2 - 1)]
    ok = (pos < m2) & ((cand >> shift) == frag) & (cand != ones)
    pay = cand & payload_mask
    best = ((pay >> np.uint64(c_bits)) << np.uint64(32)) | (pay & eid_mask)
    return jnp.where(ok, best, INF_U64)


def _elect_pallas(cs, cd, key, *, num_segments, block, interpret):
    """Kernel election: fragment-sort both directions, masked scan, run-end
    extraction with a conflict-free scatter (each slot written once)."""
    seg2 = jnp.concatenate([cs, cd]).astype(jnp.int32)
    oth2 = jnp.concatenate([cd, cs]).astype(jnp.int32)
    hi, lo = keys_lib.split_key_lanes(key)
    hi2 = jnp.concatenate([hi, hi])
    lo2 = jnp.concatenate([lo, lo])
    order = jnp.argsort(seg2)
    seg2, oth2, hi2, lo2 = (seg2[order], oth2[order], hi2[order], lo2[order])
    pad = (-seg2.shape[0]) % block
    if pad:
        # Padding lanes carry seg == oth, dead by the kernel's own mask.
        seg2 = jnp.concatenate([seg2, jnp.full(pad, _PAD_SEG, jnp.int32)])
        oth2 = jnp.concatenate([oth2, jnp.full(pad, _PAD_SEG, jnp.int32)])
        inf32 = jnp.full(pad, np.uint32(0xFFFFFFFF), jnp.uint32)
        hi2 = jnp.concatenate([hi2, inf32])
        lo2 = jnp.concatenate([lo2, inf32])
    shi, slo = masked_minplus_scan(seg2, oth2, hi2, lo2, block=block,
                                   interpret=interpret)
    scan = keys_lib.combine_key_lanes(shi, slo)
    nxt = jnp.concatenate([seg2[1:], jnp.full(1, -3, jnp.int32)])
    run_end = seg2 != nxt
    out = jnp.full((num_segments,), INF_U64, jnp.uint64)
    idx = jnp.where(run_end, seg2, num_segments)
    return out.at[idx].set(jnp.where(run_end, scan, INF_U64), mode="drop")


@functools.partial(jax.jit, static_argnames=("num_segments", "lowering",
                                             "sort_bits", "block",
                                             "interpret"))
def elect(
    cs: jnp.ndarray, cd: jnp.ndarray, key: jnp.ndarray, *,
    num_segments: int, lowering: str = "scatter",
    sort_bits: "tuple[int, int] | None" = None,
    block: int = 1024, interpret: "bool | None" = None,
) -> jnp.ndarray:
    """Per-fragment minimum-outgoing-edge election over packed uint64 keys.

    ``cs``/``cd`` are the endpoint fragment labels of every edge slot;
    ``key`` the (weight-bits ‖ edge-id) packed keys.  Returns ``best`` of
    shape (num_segments,), INF_KEY where a fragment has no live edge.
    """
    if lowering not in ELECT_LOWERINGS:
        raise ValueError(f"unknown elect lowering: {lowering!r}")
    if cs.shape[0] == 0 or num_segments == 0:
        return jnp.full((num_segments,), INF_U64, jnp.uint64)
    if lowering == "sort":
        assert sort_bits is not None, "sort lowering requires sort_bits"
        return _elect_sort(cs, cd, key, num_segments=num_segments,
                           sort_bits=sort_bits)
    if lowering == "pallas":
        return _elect_pallas(cs, cd, key, num_segments=num_segments,
                             block=block, interpret=interpret)
    return ref.elect(cs, cd, key, num_segments=num_segments)


@functools.partial(jax.jit, static_argnames=(
    "num_vertices", "axis_name", "collective", "cand_cap", "num_shards"))
def connected_labels(
    src: jnp.ndarray, dst: jnp.ndarray, active: jnp.ndarray, *,
    num_vertices: int, init: "jnp.ndarray | None" = None,
    axis_name: "str | None" = None,
    collective: str = "pmin", cand_cap: "int | None" = None,
    num_shards: int = 1,
) -> jnp.ndarray:
    """Converged connected-component labels over the active edge set.

    The batched cut/cycle probe of the filter pass (DESIGN.md §10): a
    ``lax.while_loop`` of min-hooking + pointer-jump shortcut that runs
    until no active edge crosses two components.  Every iteration with a
    crossing edge strictly reduces the component count, so the loop
    terminates; the result labels each vertex with the minimum vertex id
    of its component (a canonical labeling — comparable across callers).

    ``init`` warm-starts the loop from an existing labeling whose equal
    labels are already certified connected under ``active`` — the
    incremental path of the filter's nested threshold levels (level *j*
    refines level *j-1*'s labels, so only newly-activated edges pay
    iterations).  Canonical min-id labels stay canonical under refinement.

    ``active`` must be False on padding lanes; endpoints are clipped before
    the gather so out-of-range pad vertices (``PAD_VERTEX``) are safe.
    Under ``shard_map`` pass ``axis_name`` to combine the per-shard hook
    contributions (pmin) and the per-shard liveness flag (pmax) — the
    labels are then replicated and identical on every shard.  The body is
    also vmappable (batched probes share one compiled loop).

    ``collective="compressed"`` with a static ``cand_cap`` routes the
    hook-min through the delta exchange of
    :func:`repro.sharding.collectives.pmin_compressed` (DESIGN.md §11):
    ``hook_min`` returns the identity wherever a shard hooked nothing, so
    the identity parent array is the exchange's baseline and only actual
    hook requests travel the ring.  Exact min ⇒ labels stay bit-identical.
    """
    n = num_vertices
    src = jnp.clip(src, 0, n - 1)
    dst = jnp.clip(dst, 0, n - 1)

    def crossing(comp):
        cs = comp[src]
        cd = comp[dst]
        return cs, cd, active & (cs != cd)

    def alive_any(alive):
        more = jnp.any(alive)
        if axis_name is not None:
            more = jax.lax.pmax(more.astype(jnp.int32), axis_name) > 0
        return more

    def body(carry):
        comp, _ = carry
        cs, cd, alive = crossing(comp)
        hi = jnp.maximum(cs, cd)
        lo = jnp.minimum(cs, cd)
        parent = union_find.hook_min(n, hi, lo, alive)
        if axis_name is not None:
            if collective == "compressed" and cand_cap is not None:
                from repro.sharding import collectives
                parent = collectives.pmin_compressed(
                    parent, axis_name,
                    default=jnp.arange(n, dtype=parent.dtype),
                    cap=cand_cap, num_shards=num_shards)
            else:
                parent = jax.lax.pmin(parent, axis_name)
        comp = shortcut_relabel(parent.astype(jnp.int32), comp)
        _, _, alive2 = crossing(comp)
        return comp, alive_any(alive2)

    comp0 = (jnp.arange(n, dtype=jnp.int32) if init is None
             else init.astype(jnp.int32))
    _, _, alive0 = crossing(comp0)
    comp, _ = jax.lax.while_loop(lambda c: c[1], body,
                                 (comp0, alive_any(alive0)))
    return comp


@functools.partial(jax.jit, static_argnames=(
    "num_vertices", "axis_name", "collective", "cand_cap", "num_shards"))
def component_maxkey(
    src: jnp.ndarray, dst: jnp.ndarray, key: jnp.ndarray,
    active: jnp.ndarray, *,
    num_vertices: int, init: "jnp.ndarray | None" = None,
    axis_name: "str | None" = None,
    collective: str = "pmin", cand_cap: "int | None" = None,
    num_shards: int = 1,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Packed max-key variant of :func:`connected_labels`.

    Runs the same warm-started hook/shortcut loop to convergence, then one
    scatter-MAX of the packed (weight ‖ edge-id) uint64 keys onto the
    converged labels.  Returns ``(comp, maxkey)`` where ``maxkey[v]`` is
    the maximum key of any active edge inside ``v``'s component (0 where
    the component has no active edge — 0 is unreachable by a live key
    because engine weights are positive).

    This is the swap bound of the incremental cycle rule (DESIGN.md §13):
    the component max dominates every tree-path max, so a probe edge whose
    endpoints share a component and whose key exceeds ``maxkey`` is the
    strict maximum of a cycle — provably non-MSF.  All comparisons happen
    in ONE graph's key space, so the bound is exact under weight ties too.

    ``init`` warm-starts the label loop exactly as in
    :func:`connected_labels` (the incremental pass seeds it with the top
    threshold level's labels, so the loop converges without iterating).
    Under ``shard_map`` the per-shard scatter-max combines with
    ``lax.pmax`` — exact max, so the replicated labels and bounds stay
    bit-identical at any shard count.
    """
    n = num_vertices
    comp = connected_labels(
        src, dst, active, num_vertices=n, init=init, axis_name=axis_name,
        collective=collective, cand_cap=cand_cap, num_shards=num_shards)
    # Active edges never cross components at convergence, so one endpoint
    # names the segment; inactive/padding lanes are dropped out of range.
    seg = comp[jnp.clip(src, 0, n - 1)]
    mx = jnp.zeros((n,), jnp.uint64).at[
        jnp.where(active, seg, n)
    ].max(jnp.where(active, key, jnp.uint64(0)), mode="drop")
    if axis_name is not None:
        from repro.sharding import collectives
        mx = collectives.pmax(mx, axis_name)
    return comp, mx[comp]


@jax.jit
def shortcut_relabel(parent: jnp.ndarray, comp: jnp.ndarray) -> jnp.ndarray:
    """Pointer-jumping shortcut + fragment relabel,
    ``union_find.pointer_double(parent)[comp]``, on every lowering: the
    doubling steps are data-dependent gathers over all ``n`` labels, which
    stay in XLA (no Pallas kernel — see the :mod:`.spmv_minplus`
    docstring)."""
    return ref.shortcut_relabel(parent, comp)

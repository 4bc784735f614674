"""jit'd wrappers for segment_min: sorted-scan Pallas path + scatter path."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as keys_lib
from repro.kernels.segment_min import ref
from repro.kernels.segment_min.segment_min import (
    INF_U32, segmented_min2_scan, segmented_min_scan)

INF_U64 = keys_lib.INF_KEY
_PAD_SEG = np.int32(0x7FFFFFF0)


@functools.partial(jax.jit, static_argnames=("num_segments", "block",
                                             "interpret"))
def segment_min_sorted(
    val: jnp.ndarray, seg: jnp.ndarray, *, num_segments: int,
    block: int = 1024, interpret: "bool | None" = None,
) -> jnp.ndarray:
    """Per-segment min for SORTED ``seg`` via the Pallas scan kernel.

    The run-end elements of the scan hold each segment's min; the final
    scatter is conflict-free (each output slot written exactly once)."""
    m = seg.shape[0]
    if m == 0 or num_segments == 0:
        # No input runs (or no output slots): every segment is empty and
        # gets the INF sentinel.  Never reach pallas_call with a zero grid
        # — interpret mode tolerates it, compiled lowering does not.
        return jnp.full((num_segments,), np.uint32(0xFFFFFFFF), jnp.uint32)
    pad = (-m) % block
    if pad:
        seg = jnp.concatenate([seg, jnp.full(pad, np.int32(0x7FFFFFF0), jnp.int32)])
        val = jnp.concatenate([val, jnp.full(pad, np.uint32(0xFFFFFFFF), jnp.uint32)])
    scan = segmented_min_scan(seg, val, block=block, interpret=interpret)
    nxt = jnp.concatenate([seg[1:], jnp.full(1, -3, jnp.int32)])
    run_end = seg != nxt
    out = jnp.full((num_segments,), np.uint32(0xFFFFFFFF), jnp.uint32)
    idx = jnp.where(run_end, seg, num_segments)
    return out.at[idx].set(jnp.where(run_end, scan, np.uint32(0xFFFFFFFF)), mode="drop")


@functools.partial(jax.jit, static_argnames=("num_segments", "use_pallas",
                                             "interpret"))
def segment_min(
    val: jnp.ndarray, seg: jnp.ndarray, *, num_segments: int,
    use_pallas: bool = False, interpret: "bool | None" = None,
    order: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-segment min; unsorted input. Pallas path sorts then scans.

    ``order`` — a precomputed ``argsort(seg)`` permutation.  Callers that run
    several reductions over the same segment array (e.g. the two-pass MOE
    election) sort once and pass the order in, instead of re-``argsort``-ing
    inside every call.
    """
    if not use_pallas:
        return ref.segment_min(val, seg, num_segments)
    if order is None:
        order = jnp.argsort(seg)
    return segment_min_sorted(
        val[order], seg[order], num_segments=num_segments,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_segments", "block",
                                             "interpret"))
def segment_min64_sorted(
    key: jnp.ndarray, seg: jnp.ndarray, *, num_segments: int,
    block: int = 1024, interpret: "bool | None" = None,
) -> jnp.ndarray:
    """Per-segment min over SORTED packed uint64 keys via the pair-lex
    Pallas scan — the key is split into uint32 lanes so the kernel stays in
    native VPU word width (requires x64 enabled for the uint64 in/out)."""
    m = seg.shape[0]
    if m == 0 or num_segments == 0:
        # Empty input / output: INF_KEY sentinels, no zero-grid kernel.
        return jnp.full((num_segments,), INF_U64, jnp.uint64)
    pad = (-m) % block
    if pad:
        seg = jnp.concatenate([seg, jnp.full(pad, _PAD_SEG, jnp.int32)])
        key = jnp.concatenate([key, jnp.full(pad, INF_U64, jnp.uint64)])
    hi, lo = keys_lib.split_key_lanes(key)
    shi, slo = segmented_min2_scan(seg, hi, lo, block=block,
                                  interpret=interpret)
    scan = keys_lib.combine_key_lanes(shi, slo)
    nxt = jnp.concatenate([seg[1:], jnp.full(1, -3, jnp.int32)])
    run_end = seg != nxt
    out = jnp.full((num_segments,), INF_U64, jnp.uint64)
    idx = jnp.where(run_end, seg, num_segments)
    return out.at[idx].set(jnp.where(run_end, scan, INF_U64), mode="drop")


@functools.partial(jax.jit, static_argnames=("num_segments", "use_pallas",
                                             "interpret"))
def segment_min64(
    key: jnp.ndarray, seg: jnp.ndarray, *, num_segments: int,
    use_pallas: bool = False, interpret: "bool | None" = None,
    order: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-segment min over packed uint64 keys; unsorted input.

    The fused MOE election calls this ONCE per round (both edge endpoints
    concatenated), so the Pallas path performs exactly one sort per round.

    The XLA path is a lexicographic min in two native uint32 scatter-min
    passes over the key's lanes: the weight lane first, then the edge-id
    lane of the keys that tie each segment's minimum weight.  The chip has
    no 64-bit integer unit, and a uint64 scatter-min lowers to an emulated
    pair-of-words combiner an order of magnitude slower per update.
    """
    if not use_pallas:
        if num_segments == 0:
            return jnp.full((0,), INF_U64, jnp.uint64)
        hi, lo = keys_lib.split_key_lanes(key)
        best_hi = jnp.full((num_segments,), INF_U32, jnp.uint32).at[
            seg].min(hi, mode="drop")
        # Only the ties of each segment's minimum weight land; the rest,
        # and out-of-range segments, go to slot num_segments and drop.
        tie = jnp.where(hi == best_hi[seg], seg, num_segments)
        best_lo = jnp.full((num_segments,), INF_U32, jnp.uint32).at[
            tie].min(lo, mode="drop")
        return keys_lib.combine_key_lanes(best_hi, best_lo)
    if order is None:
        order = jnp.argsort(seg)
    return segment_min64_sorted(
        key[order], seg[order], num_segments=num_segments,
        interpret=interpret)

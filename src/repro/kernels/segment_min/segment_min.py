"""Pallas TPU kernel: segmented min over sorted (segment, value) pairs.

The MST hot-spot (paper Report phase / our Borůvka MOE election): given
edges sorted by fragment id, compute the per-fragment minimum packed key.

TPU adaptation (DESIGN.md §2): no atomics on TPU, so instead of scatter-min
we run a *segmented inclusive min-scan* — Hillis–Steele log-steps inside each
VMEM block, with a (segment, running-min) carry threaded across the
sequential TPU grid in VMEM scratch.  The run-ends of the scanned array
then hold each segment's min, and a conflict-free scatter (each output
written once) finalizes — that scatter lives in ops.py as plain XLA.

Layout: the 1-D inputs are viewed as ``(m / 128, 128)`` row-major tiles, so
a block is ``block / 128`` full sublane rows of 32-bit lanes — the shape
Mosaic tiles natively.  A flat shift by ``s`` is a lane rotate plus a
one-row rotate (``s < 128``) or a pure row rotate (``s`` a multiple of
128), both ``pltpu.roll``; :func:`scan_tile` is the shared recurrence that
``spmv_minplus`` reuses with its fused liveness mask.

Values are uint32 (weight bits or tiebreak lane — the pair kernel elects
the (w, e) pair lexicographically, see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

INF_U32 = 0xFFFFFFFF           # python int: safe to close over
SENTINEL_SEG = -2              # carry init; never a real segment id
LANES = 128                    # VPU lane width
BLOCK_QUANTUM = 8 * LANES      # one (8, 128) 32-bit tile: the least block


def _shift(x, s, lane):
    """``x`` at row-major flat index ``i - s`` (cyclic; callers mask
    ``i < s``).  ``s`` is a power of two: below 128 it is a lane rotate
    whose wrapped lanes come from the previous row, else a row rotate."""
    # int32 shift amounts: Mosaic's rotate takes no i64 (the engines trace
    # under x64, where a bare Python int would become one).
    if s % LANES == 0:
        return pltpu.roll(x, jnp.int32(s // LANES), 0)
    a = pltpu.roll(x, jnp.int32(s), 1)
    return jnp.where(lane >= s, a, pltpu.roll(a, jnp.int32(1), 0))


def _lex_less(a, b):
    """Lexicographic ``a < b`` over tuples of uint32 lanes."""
    lt = a[-1] < b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        lt = (x < y) | ((x == y) & lt)
    return lt


def _last(x):
    """The tile's last element, broadcast to one ``(1, 128)`` row."""
    return jnp.broadcast_to(x[-1:, LANES - 1:], (1, LANES))


def scan_tile(seg, vals, carry_seg, carry_vals):
    """Inclusive segmented lexicographic min-scan of one ``(rows, 128)``
    tile along sorted ``seg``, folding in and advancing the cross-block
    carry held in ``(1, 128)`` VMEM refs.  Returns the scanned ``vals``.

    Grid step 0 must have initialized the carry (``SENTINEL_SEG`` / INF).
    Sortedness is what makes the endpoint test ``seg[i - s] == seg[i]``
    equivalent to "the whole span lies in one segment"."""
    inf = jnp.uint32(INF_U32)
    sentinel = jnp.int32(SENTINEL_SEG)
    lane = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1)
    flat = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0) * LANES + lane
    shift = 1
    while shift < seg.size:
        ok = flat >= shift
        sseg = jnp.where(ok, _shift(seg, shift, lane), sentinel)
        svals = tuple(jnp.where(ok, _shift(v, shift, lane), inf)
                      for v in vals)
        take = (sseg == seg) & _lex_less(svals, vals)
        vals = tuple(jnp.where(take, s, v) for s, v in zip(svals, vals))
        shift *= 2
    # Fold the carry into this block's first run.
    cvals = tuple(r[...] for r in carry_vals)
    take = (seg == carry_seg[...]) & _lex_less(cvals, vals)
    vals = tuple(jnp.where(take, c, v) for c, v in zip(cvals, vals))
    carry_seg[...] = _last(seg)
    for r, v in zip(carry_vals, vals):
        r[...] = _last(v)
    return vals


def init_carry(carry_seg, carry_vals):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_seg[...] = jnp.full(carry_seg.shape, SENTINEL_SEG, jnp.int32)
        for r in carry_vals:
            r[...] = jnp.full(r.shape, INF_U32, jnp.uint32)


def _scan_kernel(seg_ref, *refs, nvals):
    """``nvals`` value lanes in, ``nvals`` scanned lanes out, then the
    carry scratch (segment + one row per lane)."""
    in_refs, out_refs = refs[:nvals], refs[nvals:2 * nvals]
    carry_seg, carry_vals = refs[2 * nvals], refs[2 * nvals + 1:]
    init_carry(carry_seg, carry_vals)
    vals = scan_tile(seg_ref[...], tuple(r[...] for r in in_refs),
                     carry_seg, carry_vals)
    for r, v in zip(out_refs, vals):
        r[...] = v


def tiled_scan_call(kernel, seg, others, nvals, *, block, interpret):
    """Run ``kernel`` over 1-D ``seg`` + ``others`` viewed as
    ``(m / 128, 128)`` tiles on a sequential grid of ``block``-element
    blocks; returns ``nvals`` scanned uint32 lanes, 1-D again."""
    m = seg.shape[0]
    assert seg.ndim == 1 and all(a.shape == seg.shape for a in others)
    assert block % BLOCK_QUANTUM == 0, "block is whole (8, 128) tiles"
    assert m % block == 0, "caller pads to a block multiple"
    rows = block // LANES
    # int32 block index (a bare 0 is i64 under x64, which Mosaic rejects).
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, jnp.int32(0)))
    carry = pltpu.VMEM((1, LANES), jnp.uint32)
    outs = pl.pallas_call(
        kernel,
        grid=(m // block,),
        in_specs=[spec] * (1 + len(others)),
        out_specs=[spec] * nvals,
        out_shape=[jax.ShapeDtypeStruct((m // LANES, LANES), jnp.uint32)]
        * nvals,
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.int32)] + [carry] * nvals,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(interpret),
    )(*(a.reshape(m // LANES, LANES) for a in (seg, *others)))
    return tuple(o.reshape(m) for o in outs)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def segmented_min2_scan(
    seg: jnp.ndarray, hi: jnp.ndarray, lo: jnp.ndarray, *, block: int = 1024,
    interpret: "bool | None" = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Inclusive segmented lex-min scan of ``(hi, lo)`` along sorted ``seg``.

    This is the packed-key path — a uint64 key split into uint32 lanes so
    the scan stays in native VPU word width; the pair-lex min is
    associative, so the same recurrence and carry as the one-lane scan
    apply."""
    return tiled_scan_call(functools.partial(_scan_kernel, nvals=2),
                           seg, (hi, lo), 2, block=block,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def segmented_min_scan(
    seg: jnp.ndarray, val: jnp.ndarray, *, block: int = 1024,
    interpret: "bool | None" = None,
) -> jnp.ndarray:
    """Inclusive segmented min-scan of ``val`` along sorted ``seg`` runs."""
    (out,) = tiled_scan_call(functools.partial(_scan_kernel, nvals=1),
                             seg, (val,), 1, block=block,
                             interpret=interpret)
    return out

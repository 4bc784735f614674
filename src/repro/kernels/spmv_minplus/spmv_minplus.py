"""Pallas TPU kernel: the masked min-plus election of the fused Borůvka
round body (DESIGN.md §9).

The per-round MOE election is a masked min-plus segmented SpMV: for every
fragment *f*, ``best[f] = min over incident live edges of (weight ‖ edge-id)``
in the (min, +) semiring over packed keys, where a *live* edge is one whose
endpoints lie in different fragments.

:func:`masked_minplus_scan` is that reduction: a segmented pair-lex
min-scan over (weight-bits, edge-id) uint32 lanes with IN-KERNEL masking of
dead edges — the ``alive``/``where`` chain of the XLA round body never
materializes a masked key array in HBM; each tile applies the mask on the
fly and joins dead lanes to the scan as the semiring identity (INF).  It
runs ``kernels/segment_min``'s tiled pair-lex scan (same Hillis–Steele
recurrence, same VMEM carry across the sequential grid) behind the mask.

The merge shortcut that follows the election stays in XLA
(``union_find.pointer_double``): pointer doubling is a data-dependent
gather over all ``n`` labels, which Mosaic cannot lower inside a kernel
and which does not fit VMEM at engine scales.  The hook phase is a single
conflict-light n-scale scatter-min, also in XLA — see DESIGN.md §9.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.segment_min.segment_min import (
    INF_U32, init_carry, scan_tile, tiled_scan_call)


def _minplus_kernel(seg_ref, oth_ref, hi_ref, lo_ref, ohi_ref, olo_ref,
                    carry_seg, carry_hi, carry_lo):
    """Masked segmented pair-lex min-scan tile (see module docstring).

    ``seg`` is the reducing-side fragment label (sorted), ``oth`` the other
    endpoint's fragment label riding along unsorted-in-value — the mask
    ``seg != oth`` is the Borůvka liveness test, applied here instead of in
    a separate XLA ``where`` sweep.  Padding lanes carry ``seg == oth`` (the
    ops layer pads both with the same sentinel), so they are dead by the
    same test and need no third sentinel convention.
    """
    init_carry(carry_seg, (carry_hi, carry_lo))
    inf = jnp.uint32(INF_U32)
    seg = seg_ref[...]
    hi = hi_ref[...]
    lo = lo_ref[...]
    # In-kernel masking: dead edges (endpoints in one fragment, or the INF
    # padding key) join the scan as the semiring identity.
    live = (seg != oth_ref[...]) & jnp.logical_not((hi == inf) & (lo == inf))
    hi, lo = scan_tile(seg, (jnp.where(live, hi, inf),
                             jnp.where(live, lo, inf)),
                       carry_seg, (carry_hi, carry_lo))
    ohi_ref[...] = hi
    olo_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def masked_minplus_scan(
    seg: jnp.ndarray, oth: jnp.ndarray, hi: jnp.ndarray, lo: jnp.ndarray,
    *, block: int = 1024, interpret: "bool | None" = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Masked inclusive segmented lex-min scan along sorted ``seg`` runs.

    Lanes where ``seg == oth`` (dead edges) or ``(hi, lo) == INF`` (padding)
    contribute the identity.  The run-end elements hold each segment's
    masked min; the ops layer finalizes with a conflict-free scatter.
    """
    return tiled_scan_call(_minplus_kernel, seg, (oth, hi, lo), 2,
                           block=block, interpret=interpret)

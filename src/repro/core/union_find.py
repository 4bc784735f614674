"""Hooking + pointer-jumping primitives (JAX, fixed-shape, collective-safe).

This is the synchronous stand-in for GHS fragment merging: min-hooking builds
a strictly-decreasing parent forest (no cycles by construction), and pointer
doubling compresses it — the O(log) collapse of the GHS ``Initiate``
broadcast described in DESIGN.md §3.  Doubling stops at its fixpoint: a
forest of depth d is flat after ⌈log2 d⌉ steps, and one more step, whose
gather changes nothing, confirms it.  The step count is capped at
⌈log2 N⌉, the most any forest over N vertices needs, so a chain of N
vertices runs exactly the cap.

:func:`pointer_double` is a ``jax.jit`` of its own, as ``segment_min64``
is, so that a device trace names its gathers ``pointer_double/gather``.
Called from inside another jitted program it is inlined: the compiled
program and its results are those of the plain function, and its ops keep
the caller's ``named_scope``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

INF32 = jnp.uint32(0xFFFFFFFF)


def hook_min(
    n: int, hi: jnp.ndarray, lo: jnp.ndarray, valid: jnp.ndarray
) -> jnp.ndarray:
    """Scatter-min hooking: parent[hi] = min(lo) over valid merge requests.

    ``hi > lo`` must hold for valid entries; invalid entries are inert.
    Returns the local parent contribution (combine across shards with pmin).
    """
    parent = jnp.arange(n, dtype=jnp.uint32)
    hi_idx = jnp.where(valid, hi, n)  # out-of-range drops the update
    return parent.at[hi_idx].min(jnp.where(valid, lo.astype(jnp.uint32), INF32),
                                 mode="drop")


@functools.partial(jax.jit, static_argnames=("num_steps",))
def pointer_double(
    parent: jnp.ndarray, num_steps: int | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Path compression by pointer doubling, to the fixpoint.

    Each step is one gather, ``q = p[p]``; the loop carries ``q`` and
    whether any entry moved, and stops once nothing did or after
    ``num_steps`` steps (default ⌈log2 N⌉).  Returns the compressed parent
    and the number of steps run (int32): ⌈log2 depth⌉ + 1 below the cap.
    The result equals ``num_steps`` unconditional steps, since doubling a
    flat forest changes nothing.  Under ``vmap`` the loop runs until every
    lane has stopped, and each lane reports its own count; under
    ``shard_map`` a replicated parent gives every shard the same exit.
    """
    n = parent.shape[0]
    if num_steps is None:
        num_steps = max(1, math.ceil(math.log2(max(n, 2))))

    def cond(c):
        i, _, moved = c
        return moved & (i < num_steps)

    def body(c):
        i, p, _ = c
        q = p[p]
        return i + 1, q, jnp.any(q != p)

    steps, parent, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), parent, jnp.bool_(True)))
    return parent, steps

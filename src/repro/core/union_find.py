"""Hooking + pointer-jumping primitives (JAX, fixed-shape, collective-safe).

This is the synchronous stand-in for GHS fragment merging: min-hooking builds
a strictly-decreasing parent forest (no cycles by construction), and pointer
doubling compresses it in ⌈log2 N⌉ steps — the O(log) collapse of the GHS
``Initiate`` broadcast described in DESIGN.md §3.

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
def pointer_double(parent: jnp.ndarray, num_steps: int | None = None) -> jnp.ndarray:
    """Full path compression by pointer doubling (⌈log2 N⌉ gathers)."""
    n = parent.shape[0]
    if num_steps is None:
        num_steps = max(1, math.ceil(math.log2(max(n, 2))))

    def body(_, p):
        return p[p]

    return jax.lax.fori_loop(0, num_steps, body, parent)

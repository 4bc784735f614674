"""Sharded collectives: compressed MST reductions + LM-gradient helpers.

Two families live here:

**MST-facing compressed reductions** (DESIGN.md §11).  The per-round
exchange of the distributed Borůvka engines is an elementwise ``pmin``
over a replicated length-``n`` array (fragment MOE keys, hook parents) —
but each shard only *improves* the entries its local edges touch, and that
count shrinks geometrically as fragments merge and edges die.
:func:`pmin_compressed` exploits the sparsity: each shard packs its
improved ``(index, value)`` pairs into a static-``cap`` candidate list and
the lists travel a ``ppermute`` store-and-forward ring (P-1 steps, every
shard scatter-mins every other shard's original packet exactly once).
The reduction is an exact min over the identical value set, so the result
is bit-identical to ``lax.pmin`` by construction; when any shard's
candidate count overflows ``cap``, a replicated flag routes the WHOLE
step through ``lax.pmin`` (the fallback contract — never a truncated
exchange).  This mirrors the paper's message-compression optimization:
the exchange is sized by what changed, not by the vertex count.

**LM-gradient helpers** (the module's original residents).
``compress_tree`` casts gradients to bf16 with error feedback *before*
the data-parallel reduction XLA inserts (halving DP all-reduce bytes);
the residual rides in the optimizer state so the update is unbiased over
time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

COLLECTIVES = ("pmin", "compressed")

# Wire format of one candidate entry: int32 index lane + the value lane.
INDEX_BYTES = 4


def resolve_collective(collective: str) -> str:
    """Validate the shared ``params.collective`` knob."""
    if collective not in COLLECTIVES:
        raise ValueError(
            f"unknown collective {collective!r}; options: {COLLECTIVES}")
    return collective


def _lanes(x):
    """(high, low) uint32 lanes of a uint64 array."""
    return ((x >> jnp.uint64(32)).astype(jnp.uint32),
            (x & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))


def _join(hi, lo):
    return (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(jnp.uint64)


def pmin(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """``lax.pmin`` that also lowers for uint64 on the TPU.

    XLA:TPU all-reduces 64-bit values by sum only, so a uint64 min runs as
    two uint32 reductions: the high lane first, then the low lane among
    the shards that hold the winning high lane.  Unsigned order is the
    lexicographic order of the lanes, so the result is bit-identical."""
    if x.dtype != jnp.uint64:
        return jax.lax.pmin(x, axis_name)
    hi, lo = _lanes(x)
    hi_min = jax.lax.pmin(hi, axis_name)
    lo_min = jax.lax.pmin(
        jnp.where(hi == hi_min, lo, jnp.uint32(0xFFFFFFFF)), axis_name)
    return _join(hi_min, lo_min)


def pmax(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """``lax.pmax`` that also lowers for uint64 on the TPU (see
    :func:`pmin`)."""
    if x.dtype != jnp.uint64:
        return jax.lax.pmax(x, axis_name)
    hi, lo = _lanes(x)
    hi_max = jax.lax.pmax(hi, axis_name)
    lo_max = jax.lax.pmax(jnp.where(hi == hi_max, lo, jnp.uint32(0)),
                          axis_name)
    return _join(hi_max, lo_max)


def pmin_compressed(
    x: jnp.ndarray,
    axis_name: str,
    *,
    default: jnp.ndarray,
    cap: int,
    num_shards: int,
) -> jnp.ndarray:
    """Elementwise min over ``axis_name``, exchanging only improved entries.

    ``x`` is a per-shard length-``n`` array whose entries equal ``default``
    (a scalar sentinel like ``INF_KEY``, or an elementwise baseline like
    the identity parent array — any shape broadcastable against ``x``)
    wherever the shard contributed nothing this round.  Each shard packs
    the positions where ``x != default`` into a ``(cap,)`` candidate list
    of ``(int32 index, value)`` pairs; the packets ride a store-and-forward
    ``ppermute`` ring for ``num_shards - 1`` steps, so every shard
    scatter-mins every other shard's *original* packet exactly once.

    Exactness: the result at index ``i`` is the min over all shards'
    contributed values and the (shard-agreed) baseline — the same value
    set ``lax.pmin`` reduces, in a different order, and min over uint keys
    is order-free, so the output is bit-identical.  If ANY shard holds
    more than ``cap`` candidates, a pmax-replicated overflow flag sends
    every shard through plain ``lax.pmin`` for this call (the fallback
    contract); ``cap`` therefore tunes bytes, never correctness.
    """
    if num_shards <= 1:
        return x
    n = x.shape[0]
    has = x != default
    count = has.sum(dtype=jnp.int32)
    overflow = jax.lax.pmax((count > cap).astype(jnp.int32), axis_name) > 0

    def full(x):
        return pmin(x, axis_name)

    def ring(x):
        pos = jnp.cumsum(has.astype(jnp.int32)) - 1
        idx = jnp.where(has, pos, cap)          # cap → scatter-dropped
        # Index sentinel n is out of range for the accumulator scatter, so
        # unused packet slots are inert on the receiving side too.
        frag = jnp.full((cap,), n, jnp.int32).at[idx].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        val = jnp.zeros((cap,), x.dtype).at[idx].set(x, mode="drop")
        acc = x
        perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]
        for _ in range(num_shards - 1):
            frag = jax.lax.ppermute(frag, axis_name, perm)
            val = jax.lax.ppermute(val, axis_name, perm)
            acc = acc.at[frag].min(val, mode="drop")
        return acc

    return jax.lax.cond(overflow, full, ring, x)


def compressed_bytes(cap: int, num_shards: int, value_bytes: int) -> int:
    """Per-shard on-wire bytes of ONE compressed exchange: ``num_shards-1``
    ring steps each forwarding a ``cap``-entry packet."""
    if num_shards <= 1:
        return 0
    return (num_shards - 1) * cap * (INDEX_BYTES + value_bytes)


def dense_bytes(n: int, num_shards: int, value_bytes: int) -> int:
    """Per-shard on-wire bytes of one full-width ``lax.pmin`` over a
    replicated length-``n`` array, under the bandwidth-optimal
    reduce-scatter + all-gather model: ``2·(P-1)/P · n`` values."""
    if num_shards <= 1:
        return 0
    return int(2 * (num_shards - 1) * n * value_bytes // num_shards)


def compress_tree(grads, residual):
    """bf16 compression with error feedback. residual=None -> zeros."""
    if residual is None:
        residual = jax.tree.map(
            lambda g: jnp.zeros_like(g, jnp.float32), grads)

    def comp(g, r):
        gf = g.astype(jnp.float32) + r
        gc = gf.astype(jnp.bfloat16)
        return gc, gf - gc.astype(jnp.float32)

    pairs = jax.tree.map(comp, grads, residual)
    comp_g = jax.tree.map(lambda p: p[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    new_res = jax.tree.map(lambda p: p[1], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
    return comp_g, new_res

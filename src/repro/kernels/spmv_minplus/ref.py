"""Pure-XLA oracles for the fused Borůvka round body (spmv_minplus)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import keys as keys_lib
from repro.core import union_find

INF_KEY = keys_lib.INF_KEY


def elect(cs: jnp.ndarray, cd: jnp.ndarray, key: jnp.ndarray,
          *, num_segments: int) -> jnp.ndarray:
    """Masked min-plus election oracle: per-fragment min packed key.

    An edge is live iff its endpoint fragments differ and its key is not the
    INF padding sentinel; dead edges contribute the semiring identity.  Both
    edge directions reduce in one pair of scatter-mins (the XLA lowering the
    kernels are benchmarked against).
    """
    alive = (cs != cd) & (key != INF_KEY)
    k = jnp.where(alive, key, INF_KEY)
    out = jnp.full((num_segments,), INF_KEY, jnp.uint64)
    out = out.at[cs].min(k, mode="drop")
    return out.at[cd].min(k, mode="drop")


def shortcut_relabel(parent: jnp.ndarray, comp: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the fused shortcut: full pointer doubling, then relabel."""
    return union_find.pointer_double(parent)[0][comp]

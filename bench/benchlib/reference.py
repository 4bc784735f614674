"""The plain reference: minimum spanning forests computed without the
program, and the comparison of forests as edge sets.

The order is the one the configurations state: weight first, then the
canonical edge id (the edge's position in the canonical edge list, sorted
by ``(u, v)``).  Ranks under that order are distinct, so handing them to
SciPy's Kruskal as weights makes the forest unique whatever SciPy does
with ties.

``precision="bfloat16"`` is the control: the same computation with every
weight first rounded to bfloat16, the next precision below the float32
the configurations state.
"""
from __future__ import annotations

import numpy as np

PRECISIONS = ("float32", "bfloat16")


def to_bfloat16(w: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept as
    float32 values."""
    bits = np.asarray(w, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + np.uint64(0x7FFF) + ((bits >> np.uint64(16))
                                        & np.uint64(1))) >> np.uint64(16)
    return (bits << np.uint64(16)).astype(np.uint32).view(np.float32)


def msf_edge_ids(num_vertices: int, src, dst, weight,
                 precision: str = "float32") -> np.ndarray:
    """Sorted canonical ids of the minimum spanning forest's edges."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import minimum_spanning_tree

    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    m = len(src)
    if m == 0:
        return np.zeros(0, np.int64)
    w = np.asarray(weight, np.float32)
    if precision == "bfloat16":
        w = to_bfloat16(w)
    order = np.lexsort((np.arange(m), w))
    rank = np.empty(m, np.float64)
    rank[order] = np.arange(1, m + 1, dtype=np.float64)
    graph = csr_matrix((rank, (np.asarray(src, np.int64),
                               np.asarray(dst, np.int64))),
                       shape=(num_vertices, num_vertices))
    tree = minimum_spanning_tree(graph).tocoo()
    return np.sort(order[tree.data.astype(np.int64) - 1])


def edge_rows(src, dst, weight) -> tuple:
    """A forest as ``(pair ids, weight bits)`` sorted by pair id, where an
    edge's pair id is ``min(u, v) << 32 | max(u, v)``."""
    s = np.asarray(src, np.int64)
    d = np.asarray(dst, np.int64)
    pid = ((np.minimum(s, d).astype(np.uint64) << np.uint64(32))
           | np.maximum(s, d).astype(np.uint64))
    bits = np.asarray(weight, np.float32).view(np.uint32)
    order = np.argsort(pid, kind="stable")
    return pid[order], bits[order]


def wrong_edges(got: tuple, want: tuple) -> int:
    """Edges in one forest and not in the other (symmetric difference of
    the ``(u, v, weight)`` sets; an edge given twice counts once as
    wrong)."""
    (pa, wa), (pb, wb) = got, want
    common, ia, ib = np.intersect1d(pa, pb, return_indices=True)
    differ = int(np.count_nonzero(wa[ia] != wb[ib]))
    return (len(pa) - len(common)) + (len(pb) - len(common)) + 2 * differ

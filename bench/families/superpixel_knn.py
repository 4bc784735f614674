"""Superpixel graphs after the CIFAR10 graphs of Dwivedi et al.,
"Benchmarking Graph Neural Networks" (arXiv:2003.00982): each image's
superpixels are nodes, joined to their k nearest neighbours.

Generator parameters (a configuration's ``generator``): ``min_nodes``,
``max_nodes`` (node count drawn uniformly between them) and ``k``.  Each
node has a centroid in the unit square and a mean colour in the unit
cube; edges join each node to its ``k`` nearest centroids, symmetrised
(the union of both directions).  An edge's weight is the distance of its
two nodes' five features (centroid and colour) over its largest possible
value, quantised to ``(bits23 + 0.5) * 2**-23`` so that it lies in (0, 1)
and is exact in float32.  Edges are canonical: ``u < v``, sorted by
``(u, v)``.
"""
from __future__ import annotations

import numpy as np

_FEATURES = 5          # centroid (x, y) and colour (r, g, b)


def draw(gen: dict, rng: np.random.Generator):
    """One graph: ``(n, src, dst, weight)``."""
    n = int(rng.integers(int(gen["min_nodes"]), int(gen["max_nodes"]) + 1))
    k = min(int(gen["k"]), n - 1)
    feat = rng.random((n, _FEATURES))
    xy = feat[:, :2]
    d2 = ((xy[:, None, :] - xy[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    nbr = np.argpartition(d2, k - 1, axis=1)[:, :k]
    i = np.repeat(np.arange(n), k)
    j = nbr.ravel()
    u, v = np.minimum(i, j), np.maximum(i, j)
    pid = np.unique(u.astype(np.int64) * n + v)
    u, v = pid // n, pid % n
    dist = np.sqrt(((feat[u] - feat[v]) ** 2).sum(-1)) / np.sqrt(_FEATURES)
    bits = np.clip(np.floor(dist * 2.0 ** 23), 0, 2 ** 23 - 1)
    w = ((bits.astype(np.float32) + np.float32(0.5))
         * np.float32(2.0 ** -23))
    return n, u.astype(np.int32), v.astype(np.int32), w


"""R-MAT graphs after the Graph500 Kronecker generator.

Generator parameters (a configuration's ``generator``): ``scale`` (2**scale
vertices), ``edgefactor`` (samples per vertex), the quadrant
probabilities ``a``, ``b``, ``c``, and ``graph_seed``.

The program builds the cell's input itself: :func:`program_input` calls
``repro.core.pipeline.build`` for the R-MAT spec these parameters name,
which draws the samples and runs the paper's §3.1 preprocessing on the
device in one jitted call.  ``graph_seed`` is fixed in the configuration,
so every run solves the same graph and the build compiles once per
checkout.

:func:`reference_graph` is the benchmark's own numpy copy of that
sampler (the Kronecker draw on the splitmix64 finalizer, the affine vertex
scramble, uniform float32 weights in (0, 1), ties included) and of §3.1
preprocessing (self-loops dropped, one copy of each vertex pair kept, the
lightest; edges sorted by ``(u, v)``, which gives each its canonical id).
It imports nothing of the program and serves the reference alone.
"""
from __future__ import annotations

import numpy as np

MASK64 = (1 << 64) - 1
_GAMMA = 0x9E3779B97F4A7C15
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_STREAM_MUL = 0xD6E8FEB86659FD93
_STREAM_XOR = 0xA5A5A5A55A5A5A5A
_SCRAMBLE_STREAMS = (97, 98)
_WEIGHT_STREAM = 64


def num_vertices(gen: dict) -> int:
    return 1 << int(gen["scale"])


def num_samples(gen: dict) -> int:
    return num_vertices(gen) * int(gen["edgefactor"])


def _splitmix64(x):
    z = x + np.uint64(_GAMMA)
    z = (z ^ (z >> np.uint64(30))) * _M1
    z = (z ^ (z >> np.uint64(27))) * _M2
    return z ^ (z >> np.uint64(31))


def _rand(seed: int, stream: int, ctr):
    """64 random bits per counter of the (seed, stream) pair."""
    base = ((seed * _GAMMA) ^ (stream * _STREAM_MUL) ^ _STREAM_XOR) & MASK64
    return _splitmix64(ctr ^ np.uint64(base))


def _unit(seed: int, stream: int, ctr):
    """(0, 1) float32 from the top 23 of 64 random bits, exactly."""
    b23 = (_rand(seed, stream, ctr) >> np.uint64(41)).astype(np.uint32)
    return (b23.astype(np.float32) + np.float32(0.5)) * np.float32(2.0 ** -23)


def raw_samples(gen: dict):
    """The generator's samples ``(src, dst, weight)``, self-loops and
    repeated pairs included."""
    seed, scale = int(gen["graph_seed"]), int(gen["scale"])
    a, b, c = float(gen["a"]), float(gen["b"]), float(gen["c"])
    t = (np.float32(a), np.float32(a + b), np.float32(a + b + c))
    ctr = np.arange(num_samples(gen), dtype=np.uint64)
    src = np.zeros(ctr.shape, np.uint64)
    dst = np.zeros(ctr.shape, np.uint64)
    one = np.uint64(1)
    for lvl in range(scale):
        r = _unit(seed, lvl, ctr)
        q = ((r >= t[0]).astype(np.uint64) + (r >= t[1]).astype(np.uint64)
             + (r >= t[2]).astype(np.uint64))
        src = (src << one) | (q >> one)
        dst = (dst << one) | (q & one)
    first = np.ones(1, np.uint64)
    mul = _rand(seed, _SCRAMBLE_STREAMS[0], first)[0] | one
    add = _rand(seed, _SCRAMBLE_STREAMS[1], first)[0]
    mask = np.uint64(num_vertices(gen) - 1)
    src = (src * mul + add) & mask
    dst = (dst * mul + add) & mask
    return src, dst, _unit(seed, _WEIGHT_STREAM, ctr)


def reference_graph(gen: dict):
    """The canonical graph after §3.1, on the host: ``(n, src, dst,
    weight)`` with ``src < dst``, sorted by ``(src, dst)``."""
    s, d, w = raw_samples(gen)
    keep = s != d
    s, d, w = s[keep], d[keep], w[keep]
    u, v = np.minimum(s, d), np.maximum(s, d)
    pid = (u << np.uint64(32)) | v
    order = np.lexsort((w, pid))
    pid, w = pid[order], w[order]
    first = np.ones(len(pid), bool)
    first[1:] = pid[1:] != pid[:-1]
    pid, w = pid[first], w[first]
    return (num_vertices(gen), (pid >> np.uint64(32)).astype(np.int64),
            (pid & np.uint64(0xFFFFFFFF)).astype(np.int64), w)


def program_input(gen: dict, mesh=None):
    """The cell's input as the program builds it: ``pipeline.build`` of
    the R-MAT spec, resident on the device."""
    from repro.core import pipeline

    spec = pipeline.GraphSpec("rmat", int(gen["scale"]),
                              avg_degree=2 * int(gen["edgefactor"]),
                              seed=int(gen["graph_seed"]))
    return pipeline.build(spec, mesh)

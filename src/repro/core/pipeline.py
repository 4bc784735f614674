"""Device-resident graph pipeline: sharded generation → §3.1 preprocessing →
engine hand-off without an edge round-trip through host memory (DESIGN.md §7).

PRs 1–2 made both MST engines device-resident, which left the host-side
numpy graph layer (Python-loop generators, ``np.lexsort`` dedup, host pad +
upload) dominating end-to-end wall clock.  This module moves the whole
build onto the accelerator:

* **Counter-based generation.**  Every sampler is a pure function of
  ``(seed, sample index)`` built on the splitmix64 finalizer, written ONCE
  against the array namespace (``numpy`` or ``jax.numpy``) — the same code
  runs as the host oracle and as the jitted device builder, so the two are
  *byte-identical* by construction, for any shard count (sample *i* never
  depends on its neighbors).  Weights are ``(bits23 + 0.5) · 2⁻²³`` — every
  float op is exact (or a single correctly-rounded IEEE op), so numpy and
  XLA agree bit-for-bit and the open-interval (0, 1) invariant holds
  without clipping.
* **On-device §3.1 preprocessing.**  Self-loop drop + multi-edge dedup
  keeping the min-weight copy: one stable ``lexsort`` over (pair-id,
  weight) — the same packed-key order :mod:`repro.core.keys` gives the
  engines — a first-occurrence mask, and a prefix-sum stream compaction
  into a fixed-capacity canonical edge buffer.  This mirrors
  :func:`repro.core.graph.preprocess` operation-for-operation (both sorts
  are stable, identical keys ⇒ identical permutation), which is what makes
  the device pipeline's output byte-identical to the numpy oracle.
* **Sharded hand-off.**  Under a mesh every shard runs the counter-based
  build redundantly and keeps its own slice of the canonical buffer
  (``shard_map``, ZERO collectives — redundant compute is wall-clock-free
  on parallel hardware, while MB-size gathers stall XLA:CPU rendezvous),
  so the outputs carry the engines' edge sharding and
  :func:`repro.core.runtime.prepare_edges` hands :class:`DeviceEdges`
  straight to the Borůvka engine — the only host transfer in the whole
  build is ONE scalar (the deduped edge count).

Generator kinds (§4 shapes + new scenarios):

* ``rmat``    — R-MAT recursive-quadrant sampling, Graph500 parameters,
  affine odd-multiplier vertex scrambling (hub dispersal).
* ``random``  — uniform G(n, m) endpoint sampling.
* ``geo_knn`` — 2D geometric locality: vertices on a √n-side lattice, each
  sample links a vertex to a uniform neighbor in a 5×5 window, weight
  dominated by squared Euclidean distance (approximate-kNN structure).
* ``grid``    — road-like: 4-neighbor lattice links with light weights
  plus a sparse set of heavy long-range shortcuts.
* ``grid2d``  — GRID2D of Sanders & Schimek, *Engineering Massively
  Parallel MST Algorithms* (IPDPS 2023, arXiv:2302.12199): a 2-D lattice,
  each vertex joined to its 4 neighbours, uniform random weights, no
  shortcuts and no torus.
* ``chain``   — adversarial path (maximum Borůvka round count / fragment
  depth); half the samples are duplicate path edges to stress dedup.
* ``star``    — adversarial hub (every edge incident to vertex 0; each
  spoke sampled twice), the worst case for block partitioners and for the
  GHS wake-up fan-out.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

from repro.core import keys as keys_lib
from repro.core.graph import Graph, PAD_VERTEX, preprocess

KINDS = ("rmat", "random", "geo_knn", "grid", "grid2d", "chain", "star")

# R-MAT quadrant thresholds (a=0.57, b=0.19, c=0.19 — Graph500).
_RMAT_T = (np.float32(0.57), np.float32(0.76), np.float32(0.95))
_GEO_WINDOW = 2                     # 5×5 neighbor window
_GRID2D_WEIGHT_STREAM = 99          # used by no other sampler
_MASK64 = (1 << 64) - 1


@dataclasses.dataclass(frozen=True)
class GraphSpec:
    """Static description of one generated graph (hashable ⇒ jit-cacheable)."""

    kind: str
    scale: int                      # log2(num_vertices), paper convention
    avg_degree: int = 32
    seed: int = 0

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown generator kind {self.kind!r}; options: {KINDS}")
        if not 1 <= self.scale <= 26:
            # scale 0 has no valid chain/star edge; > 26 overflows the
            # narrow-key/pid packings and any realistic sample buffer.
            raise ValueError(f"scale must be in [1, 26], got {self.scale}")

    @property
    def num_vertices(self) -> int:
        return 1 << self.scale

    @property
    def num_samples(self) -> int:
        """Raw (possibly loop/multi-edge) samples drawn, before §3.1."""
        n = self.num_vertices
        if self.kind in ("rmat", "random", "geo_knn"):
            return n * self.avg_degree // 2
        if self.kind == "grid":
            return 2 * n + max(n // 16, 1)      # lattice links + shortcuts
        if self.kind == "grid2d":
            return 2 * n                        # right links, down links
        return 2 * max(n - 1, 1)                # chain / star: spokes twice


# ---------------------------------------------------------------------------
# Counter-based RNG — shared numpy / jax.numpy implementation
# ---------------------------------------------------------------------------

def _stream_base(seed: int, stream: int) -> np.uint64:
    """Per-(seed, stream) xor constant, computed in exact Python ints."""
    return np.uint64(
        ((seed * 0x9E3779B97F4A7C15) ^ (stream * 0xD6E8FEB86659FD93)
         ^ 0xA5A5A5A55A5A5A5A) & _MASK64)


def _rand_u64(seed: int, stream: int, ctr):
    """splitmix64 (keys.py finalizer) over a uint64 counter array."""
    return keys_lib.splitmix64(ctr ^ _stream_base(seed, stream))


def _to_f32_unit(bits23):
    """Exact (0, 1) float32 from 23 random bits: every op IEEE-exact or a
    single correctly-rounded op — numpy and XLA agree bit-for-bit."""
    return ((bits23.astype(np.float32) + np.float32(0.5))
            * np.float32(2.0 ** -23))


def _unif01(seed: int, stream: int, ctr):
    return _to_f32_unit((_rand_u64(seed, stream, ctr)
                         >> np.uint64(41)).astype(np.uint32))


# ---------------------------------------------------------------------------
# Samplers — pure (seed, counter) → (src u64, dst u64, weight f32)
# ---------------------------------------------------------------------------
# Invalid samples are emitted as self-loops; §3.1 preprocessing drops them.

def _sample_rmat(xp, spec: GraphSpec, ctr):
    n, seed = spec.num_vertices, spec.seed
    src = xp.zeros(ctr.shape, np.uint64)
    dst = xp.zeros(ctr.shape, np.uint64)
    for lvl in range(spec.scale):
        r = _unif01(seed, lvl, ctr)
        q = ((r >= _RMAT_T[0]).astype(np.uint64)
             + (r >= _RMAT_T[1]).astype(np.uint64)
             + (r >= _RMAT_T[2]).astype(np.uint64))
        src = (src << np.uint64(1)) | (q >> np.uint64(1))
        dst = (dst << np.uint64(1)) | (q & np.uint64(1))
    # Affine odd-multiplier scramble mod n (power of two): disperses the
    # low-id hubs across any block distribution, like Graph500's permutation.
    one = np.asarray([1], np.uint64)        # array, not scalar: silent wrap
    a = int(_rand_u64(seed, 97, one)[0]) | 1
    b = int(_rand_u64(seed, 98, one)[0])
    mul, add, mask = (np.uint64(a & _MASK64), np.uint64(b & _MASK64),
                      np.uint64(n - 1))
    src = (src * mul + add) & mask
    dst = (dst * mul + add) & mask
    return src, dst, _unif01(seed, 64, ctr)


def _sample_random(xp, spec: GraphSpec, ctr):
    n, seed = spec.num_vertices, spec.seed
    mask = np.uint64(n - 1)
    src = _rand_u64(seed, 0, ctr) & mask
    dst = _rand_u64(seed, 1, ctr) & mask
    return src, dst, _unif01(seed, 2, ctr)


def _sample_geo_knn(xp, spec: GraphSpec, ctr):
    n, seed = spec.num_vertices, spec.seed
    side = 1 << (spec.scale // 2)
    rows = n // side
    W = _GEO_WINDOW
    u = _rand_u64(seed, 0, ctr) & np.uint64(n - 1)
    dx = (_rand_u64(seed, 1, ctr) % np.uint64(2 * W + 1)).astype(np.int64) - W
    dy = (_rand_u64(seed, 2, ctr) % np.uint64(2 * W + 1)).astype(np.int64) - W
    vx = (u % np.uint64(side)).astype(np.int64)
    vy = (u // np.uint64(side)).astype(np.int64)
    nx = xp.clip(vx + dx, 0, side - 1)
    ny = xp.clip(vy + dy, 0, rows - 1)
    v = (ny * side + nx).astype(np.uint64)
    dist2 = ((nx - vx) ** 2 + (ny - vy) ** 2).astype(np.uint64)   # ≤ 2W²
    # weight bits: distance-dominant high lane, hash jitter low lane — stays
    # ≤ 2²³ so the int→f32 conversion is exact.
    wbits = ((dist2 << np.uint64(19))
             | (_rand_u64(seed, 3, ctr) & np.uint64((1 << 19) - 1)))
    return u, v, _to_f32_unit(wbits.astype(np.uint32))


def _lattice_link(xp, spec: GraphSpec, ctr):
    """Counter ``c < n`` is vertex ``c``'s right link on a lattice of
    ``2**(scale // 2)`` columns, ``n ≤ c < 2n`` the down link of vertex
    ``c - n``.  Border links clamp to self-loops (dropped): no torus."""
    n = spec.num_vertices
    side = 1 << (spec.scale // 2)
    rows = n // side
    v = ctr & np.uint64(n - 1)
    vx = v % np.uint64(side)
    vy = v // np.uint64(side)
    right = xp.where(vx < np.uint64(side - 1), v + np.uint64(1), v)
    down = xp.where(vy < np.uint64(rows - 1), v + np.uint64(side), v)
    return v, xp.where(ctr < np.uint64(n), right, down)


def _sample_grid(xp, spec: GraphSpec, ctr):
    n, seed = spec.num_vertices, spec.seed
    lattice = ctr < np.uint64(2 * n)
    v, link = _lattice_link(xp, spec, ctr)
    su = _rand_u64(seed, 10, ctr) & np.uint64(n - 1)
    sv = _rand_u64(seed, 11, ctr) & np.uint64(n - 1)
    src = xp.where(lattice, v, su)
    dst = xp.where(lattice, link, sv)
    # Lattice roads are light (< 0.5); shortcuts are heavy (≥ 0.5) highways.
    bits22 = _rand_u64(seed, 12, ctr) & np.uint64((1 << 22) - 1)
    wbits = xp.where(lattice, bits22, bits22 | np.uint64(1 << 22))
    return src, dst, _to_f32_unit(wbits.astype(np.uint32))


def _sample_grid2d(xp, spec: GraphSpec, ctr):
    """The lattice's links alone; weights are uniform 23-bit, so ties
    occur."""
    v, link = _lattice_link(xp, spec, ctr)
    return v, link, _unif01(spec.seed, _GRID2D_WEIGHT_STREAM, ctr)


def _sample_chain(xp, spec: GraphSpec, ctr):
    n, seed = spec.num_vertices, spec.seed
    links = max(n - 1, 1)
    j = xp.where(ctr < np.uint64(links), ctr,
                 _rand_u64(seed, 5, ctr) % np.uint64(links))
    return j, j + np.uint64(1), _unif01(seed, 6, ctr)


def _sample_star(xp, spec: GraphSpec, ctr):
    n, seed = spec.num_vertices, spec.seed
    spoke = (ctr % np.uint64(max(n - 1, 1))) + np.uint64(1)
    return xp.zeros(ctr.shape, np.uint64), spoke, _unif01(seed, 7, ctr)


_SAMPLERS = {
    "rmat": _sample_rmat,
    "random": _sample_random,
    "geo_knn": _sample_geo_knn,
    "grid": _sample_grid,
    "grid2d": _sample_grid2d,
    "chain": _sample_chain,
    "star": _sample_star,
}


def raw_samples(spec: GraphSpec, xp=np, ctr=None):
    """Raw (src, dst, weight) samples under ``xp`` ∈ {numpy, jax.numpy}."""
    if ctr is None:
        ctr = xp.arange(spec.num_samples, dtype=np.uint64)
    return _SAMPLERS[spec.kind](xp, spec, ctr)


# ---------------------------------------------------------------------------
# Edge sampling (DESIGN.md §10) — the Filter-Borůvka counter-based sampler
# ---------------------------------------------------------------------------
# A sample decision is a pure function of (seed, canonical edge id) built on
# the same splitmix64 finalizer as the generators, written once against the
# array namespace — so the numpy oracle and any jitted/sharded evaluation are
# byte-identical, and the decision for edge i never depends on which shard
# holds it (determinism at ANY shard count, the §10 contract).

_SAMPLE_STREAM = 0x5A17                 # disjoint from generator streams

def sample_mask(seed: int, rate: float, eid):
    """Bernoulli(rate) keep-mask over canonical edge ids (numpy or jnp).

    ``eid`` is a uint64 array of canonical edge ids; ``rate`` is a host
    float.  Endpoints are exact: rate ≤ 0 keeps nothing (the empty-sample
    path the filter driver must survive), rate ≥ 1 keeps everything.
    """
    eid = eid.astype(np.uint64)
    if rate <= 0.0:
        return eid != eid
    if rate >= 1.0:
        return eid == eid
    thresh = np.uint64(int(rate * 2.0 ** 64))
    return _rand_u64(seed, _SAMPLE_STREAM, eid) < thresh


def sample_mask_fixed_k(xp, seed: int, k: int, eid):
    """Fixed-size variant: keep exactly the ``k`` smallest splitmix64 draws.

    The k-th draw is a GLOBAL order statistic, so this must be evaluated
    over the full edge-id range to stay shard-count invariant (the driver
    defaults to the Bernoulli form, which needs no global pass)."""
    eid = eid.astype(np.uint64)
    if k <= 0:
        return eid != eid
    if k >= int(eid.shape[0]):
        return eid == eid
    h = _rand_u64(seed, _SAMPLE_STREAM, eid)
    kth = xp.sort(h)[k - 1]
    return h <= kth                     # draws are distinct w.h.p.; ties only
                                        # ever widen the sample, never drop it


def sample_device_edges(edges: "DeviceEdges", rate: float, seed: int = 0):
    """Device-resident Bernoulli sample over a :class:`DeviceEdges` buffer.

    Returns a (capacity,) bool device array carrying the edge sharding of
    ``edges`` — the decision reads each slot's canonical edge id from the
    key's low lane, so it is invariant to how slots are distributed.
    Padding slots (INF keys) are never sampled.
    """
    import jax
    with jax.enable_x64():
        eid = edges.key & np.uint64(0xFFFFFFFF)
        return sample_mask(seed, rate, eid) & (edges.key != keys_lib.INF_KEY)


# ---------------------------------------------------------------------------
# Host oracle
# ---------------------------------------------------------------------------

def build_host(spec: GraphSpec) -> Graph:
    """The numpy path: same samplers, :func:`graph.preprocess` for §3.1.

    This is the oracle the device pipeline is held byte-identical to."""
    src, dst, w = raw_samples(spec, np)
    return preprocess(src, dst, w, spec.num_vertices)


# ---------------------------------------------------------------------------
# Device pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceEdges:
    """Canonical (preprocessed) edge set resident on device.

    ``src``/``dst``/``key`` have static capacity ``cap`` (a power-of-two
    multiple of the shard count); slots ≥ ``num_edges`` hold the inert
    padding sentinels (``PAD_VERTEX`` endpoints, ``INF_KEY``).  Edge *i* is
    canonical edge *i* of the byte-identical host graph: keys carry
    (weight-bits ‖ edge-id) exactly as :meth:`Graph.packed_keys` would.
    """

    num_vertices: int
    num_edges: int
    src: object                 # (cap,) int32 device array
    dst: object                 # (cap,) int32
    key: object                 # (cap,) uint64
    mesh: object = None
    spec: Optional[GraphSpec] = None

    @property
    def capacity(self) -> int:
        return int(self.src.shape[0])

    @functools.cached_property
    def _host_graph(self) -> Graph:
        import jax
        m = self.num_edges
        src, dst, key = jax.device_get((self.src, self.dst, self.key))
        return Graph(
            num_vertices=self.num_vertices,
            src=np.asarray(src)[:m].astype(np.int32),
            dst=np.asarray(dst)[:m].astype(np.int32),
            weight=keys_lib.unpack_weight_np(np.asarray(key)[:m]),
        )

    def to_graph(self) -> Graph:
        """Host mirror (one device→host fetch, cached) — for oracles,
        reporting, and engines that initialize on host (faithful GHS)."""
        return self._host_graph


# ---------------------------------------------------------------------------
# Batched packing (DESIGN.md §8) — many graphs per engine dispatch
# ---------------------------------------------------------------------------

BATCH_BUCKETS = ("pow2", "exact")


@dataclasses.dataclass
class GraphBatch:
    """One shape bucket of a packed multi-graph batch.

    All lanes share the padded shape ``(n_pad, cap)``: lane *r* holds graph
    ``graphs[r]`` (position ``indices[r]`` of the original sequence) with
    its canonical edges in slots ``[0, num_edges[r])`` and the inert padding
    sentinels behind them (``PAD_VERTEX`` endpoints, ``INF_KEY`` keys — the
    same invariants as single-graph padding, see :mod:`repro.core.graph`).
    Vertices ``[num_vertices[r], n_pad)`` are padding too: they own no edges,
    so they stay isolated fragments and never touch the forest.  ``slot`` is
    the per-lane slot side-lane (:func:`repro.core.partition.batched_slots`).
    """

    indices: tuple                  # positions in the caller's sequence
    graphs: tuple                   # the bucket's Graph objects, lane order
    n_pad: int
    cap: int
    num_vertices: np.ndarray        # (B,) int64
    num_edges: np.ndarray           # (B,) int64
    src: np.ndarray                 # (B, cap) int32
    dst: np.ndarray                 # (B, cap) int32
    key: np.ndarray                 # (B, cap) uint64
    slot: np.ndarray                # (B, cap) int32

    @property
    def batch_size(self) -> int:
        return len(self.indices)

    def unpack(self, mask_batch) -> list:
        """Per-lane :class:`~repro.core.kruskal_ref.ForestResult` list from
        a (B, cap) winner bitmap — ONE blocking device→host transfer for
        the whole bucket, however many graphs ride it."""
        import jax
        from repro.core import partition as partition_lib
        from repro.core import runtime as runtime_lib
        masks = np.asarray(jax.device_get(mask_batch), dtype=bool)
        out = []
        for r, g in enumerate(self.graphs):
            m = int(self.num_edges[r])
            layout = partition_lib.identity_layout(m, self.cap)
            canon = layout.canonical_mask(masks[r], m)
            res = runtime_lib.forest_from_mask(g, canon)
            res.check_consistent(g.num_vertices)
            out.append(res)
        return out


def _bucket_shape(n: int, m: int, bucket: str) -> Tuple[int, int]:
    """Padded (n_pad, cap) for one graph under a bucketing policy.

    Degenerate shapes are well-defined: an edgeless graph gets ``cap=1``
    under ``"exact"`` (one all-sentinel lane slot) but ``cap=8`` under
    ``"pow2"`` (the shared-executable floor) — both lanes solve and unpack
    to an empty forest; see the degenerate-corpus tests."""
    from repro.core.partition import pow2ceil
    if bucket == "pow2":
        return pow2ceil(max(n, 1)), pow2ceil(max(m, 8))
    return max(n, 1), max(m, 1)


def bucket_shape(
    num_vertices: int,
    num_edges: int,
    *,
    bucket: str = "pow2",
    max_vertices: Optional[int] = None,
    max_edges: Optional[int] = None,
) -> Tuple[int, int]:
    """Admission key for one graph: the padded ``(n_pad, cap)`` it would be
    packed under by :func:`pack_batch`.

    This is the incremental half of the batching contract — a serving loop
    calls it per request to route the graph into a per-shape queue without
    re-listing (or re-bucketing) everything already queued, then hands each
    queue to :func:`pack_bucket` at flush time.  Raises the same
    ``ValueError``s as :func:`pack_batch` for an unknown policy or a graph
    exceeding ``max_vertices`` / ``max_edges`` (the backpressure signal).
    """
    if bucket not in BATCH_BUCKETS:
        raise ValueError(
            f"unknown batch bucket policy {bucket!r}; options: "
            f"{BATCH_BUCKETS}")
    n, m = int(num_vertices), int(num_edges)
    if max_vertices is not None and n > max_vertices:
        raise ValueError(
            f"graph exceeds pack_batch capacity: num_vertices={n} "
            f"> max_vertices={max_vertices}")
    if max_edges is not None and m > max_edges:
        raise ValueError(
            f"graph exceeds pack_batch capacity: num_edges={m} "
            f"> max_edges={max_edges}")
    return _bucket_shape(n, m, bucket)


def pack_bucket(graphs, n_pad: int, cap: int, *,
                indices: Optional[tuple] = None) -> GraphBatch:
    """Pack an already-admitted queue of same-bucket graphs into one
    :class:`GraphBatch` — the flush half of incremental admission.

    Every graph must satisfy ``num_vertices <= n_pad`` and
    ``num_edges <= cap`` (i.e. have been routed here by
    :func:`bucket_shape`); violations raise ``ValueError``.  ``indices``
    optionally records the caller's request ordering (defaults to
    ``0..B-1``)."""
    from repro.core import partition as partition_lib

    graph_list = list(graphs)
    if not graph_list:
        raise ValueError("pack_bucket needs at least one graph")
    idxs = tuple(range(len(graph_list))) if indices is None \
        else tuple(indices)
    if len(idxs) != len(graph_list):
        raise ValueError(
            f"indices length {len(idxs)} != batch size {len(graph_list)}")
    bsz = len(graph_list)
    src = np.full((bsz, cap), PAD_VERTEX, np.int32)
    dst = np.full((bsz, cap), PAD_VERTEX, np.int32)
    key = np.full((bsz, cap), keys_lib.INF_KEY, np.uint64)
    for r, g in enumerate(graph_list):
        n, m = g.num_vertices, g.num_edges
        if n > n_pad or m > cap:
            raise ValueError(
                f"lane {r} does not fit bucket ({n_pad}, {cap}): "
                f"num_vertices={n}, num_edges={m}")
        src[r, :m] = g.src
        dst[r, :m] = g.dst
        key[r, :m] = g.packed_keys
    return GraphBatch(
        indices=idxs,
        graphs=tuple(graph_list),
        n_pad=int(n_pad), cap=int(cap),
        num_vertices=np.array(
            [g.num_vertices for g in graph_list], np.int64),
        num_edges=np.array([g.num_edges for g in graph_list], np.int64),
        src=src, dst=dst, key=key,
        slot=partition_lib.batched_slots(bsz, cap))


def pack_batch(
    graphs,
    *,
    bucket: str = "pow2",
    max_vertices: Optional[int] = None,
    max_edges: Optional[int] = None,
) -> list:
    """Bucket ``graphs`` by padded shape and pack each bucket into
    leading-axis-stacked arrays ready for the vmapped engine.

    ``bucket`` — ``"pow2"`` (default) rounds each graph's (n, m) up to
    powers of two so mixed sizes share executables; ``"exact"`` buckets
    only graphs with identical (n, m) together (no per-graph padding, one
    executable per distinct shape).  Graphs never share a bucket unless
    their padded shapes match exactly, so no lane is ever solved at the
    wrong rank.

    ``max_vertices`` / ``max_edges`` bound the padded lane shape; a graph
    exceeding either capacity raises ``ValueError`` (the serving-path
    guard: an oversized query must be rejected, not silently truncated).
    """
    if bucket not in BATCH_BUCKETS:
        raise ValueError(
            f"unknown batch bucket policy {bucket!r}; options: "
            f"{BATCH_BUCKETS}")
    graph_list = list(graphs)
    buckets: dict = {}
    for i, g in enumerate(graph_list):
        n, m = g.num_vertices, g.num_edges
        if max_vertices is not None and n > max_vertices:
            raise ValueError(
                f"graph {i} exceeds pack_batch capacity: num_vertices={n} "
                f"> max_vertices={max_vertices}")
        if max_edges is not None and m > max_edges:
            raise ValueError(
                f"graph {i} exceeds pack_batch capacity: num_edges={m} "
                f"> max_edges={max_edges}")
        buckets.setdefault(_bucket_shape(n, m, bucket), []).append(i)

    return [
        pack_bucket([graph_list[i] for i in idxs], n_pad, cap,
                    indices=tuple(idxs))
        for (n_pad, cap), idxs in sorted(buckets.items())
    ]


def _capacity(spec: GraphSpec, num_shards: int) -> int:
    """Power-of-two capacity ≥ num_samples, divisible by the shard count."""
    from repro.core.partition import pow2ceil
    return pow2ceil(-(-max(spec.num_samples, 8) // num_shards)) * num_shards


def _preprocess_device(src, dst, w, ctr, *,
                       num_samples: int, cap: int, scale: int):
    """§3.1 on device, byte-identical to :func:`graph.preprocess`.

    The numpy oracle lexsorts by (pair-id, weight) and keeps each pair's
    first copy.  Padding lanes (counter ≥ num_samples) and self-loops ride
    to the tail under an all-ones key and are dropped.

    **Narrow-key fast path** (``2·scale + 30 ≤ 64``, i.e. scale ≤ 17): a
    weight in (0, 1) has zero sign and exponent-MSB bits, so its IEEE-754
    pattern fits 30 bits and the whole (u, v, weight-bits) triple packs
    into ONE uint64 — a *key-only* sort (no payload movement, ~6x cheaper
    in XLA:CPU than a payload-carrying sort) orders pairs exactly like the
    oracle's (pair-id, weight) lexsort, and every field unpacks from the
    sorted key itself.  Each group's first lane IS its min-weight copy.

    **General path** (scale > 17): 64-bit pair-id sort carrying the weight
    as payload; the min-weight copy is recovered with a segmented
    scatter-min (among equal (pair-id, weight) lanes all payloads are
    identical, so the missing secondary sort cannot change the bytes).
    """
    import jax
    import jax.numpy as jnp
    u = jnp.minimum(src, dst)
    v = jnp.maximum(src, dst)
    drop = (u == v) | (ctr >= np.uint64(num_samples))
    slots = jnp.arange(cap, dtype=jnp.int32)

    if 2 * scale + 30 <= 64:
        wbits = w.view(jnp.uint32).astype(jnp.uint64)   # < 2**30 for (0,1)
        key = jnp.where(
            drop, keys_lib.INF_KEY,
            (u << np.uint64(scale + 30)) | (v << np.uint64(30)) | wbits)
        (key_s,) = jax.lax.sort((key,), num_keys=1)
        pid_s = key_s >> np.uint64(30)                  # (u ‖ v) lanes
        valid = key_s != keys_lib.INF_KEY
        first = valid & jnp.concatenate(
            [jnp.ones((1,), bool), pid_s[1:] != pid_s[:-1]])
        count = first.sum(dtype=jnp.int32)
        pos = jnp.cumsum(first.astype(jnp.int32)) - 1
        idx = jnp.where(first, pos, cap)
        vmask = np.uint64((1 << scale) - 1)
        u_s = (pid_s >> np.uint64(scale)).astype(jnp.int32)
        v_s = (pid_s & vmask).astype(jnp.int32)
        wb_s = (key_s & np.uint64((1 << 30) - 1)).astype(jnp.uint32)
        out_src = jnp.full((cap,), PAD_VERTEX, jnp.int32).at[idx].set(
            u_s, mode="drop")
        out_dst = jnp.full((cap,), PAD_VERTEX, jnp.int32).at[idx].set(
            v_s, mode="drop")
        out_wb = jnp.zeros((cap,), jnp.uint32).at[idx].set(wb_s, mode="drop")
        out_key = jnp.where(
            slots < count,
            (out_wb.astype(jnp.uint64) << np.uint64(32))
            | slots.astype(jnp.uint64),
            keys_lib.INF_KEY)
        return out_src, out_dst, out_key, count

    pid = jnp.where(drop, keys_lib.INF_KEY, (u << np.uint64(32)) | v)
    pid_s, w_s = jax.lax.sort((pid, w), num_keys=1)
    valid = pid_s != keys_lib.INF_KEY
    first = valid & jnp.concatenate(
        [jnp.ones((1,), bool), pid_s[1:] != pid_s[:-1]])
    count = first.sum(dtype=jnp.int32)
    # pos: canonical edge id of each lane's pair group (groups are pid-sorted
    # runs, so group rank == final edge index, as in the oracle).
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1
    minw = jnp.full((cap,), np.float32(np.inf), jnp.float32).at[
        jnp.where(valid, pos, cap)].min(w_s, mode="drop")
    idx = jnp.where(first, pos, cap)        # one representative per group
    out_src = jnp.full((cap,), PAD_VERTEX, jnp.int32).at[idx].set(
        (pid_s >> np.uint64(32)).astype(jnp.int32), mode="drop")
    out_dst = jnp.full((cap,), PAD_VERTEX, jnp.int32).at[idx].set(
        (pid_s & np.uint64(0xFFFFFFFF)).astype(jnp.int32), mode="drop")
    out_key = jnp.where(slots < count,
                        keys_lib.pack_keys(minw, slots),
                        keys_lib.INF_KEY)
    return out_src, out_dst, out_key, count


@functools.lru_cache(maxsize=64)
def _build_fn(spec: GraphSpec, cap: int, mesh):
    """Jitted generate→preprocess for one (spec, capacity, mesh).

    Under a mesh the build is **communication-free**: every shard runs the
    counter-based build over the full sample range and keeps only its own
    slice of the canonical buffer (`shard_map`, zero collectives).  The
    samplers are pure per-counter functions, so the redundancy costs no
    wall clock on real parallel hardware (each chip does the same work the
    single-device build would), while any gather/partitioned-sort strategy
    pays MB-size collectives that XLA:CPU serializes through rendezvous
    stalls — measured orders of magnitude slower at these sizes.  A true
    distributed sample-sort is future work; byte-identity is unaffected
    either way (the sliced result IS the single-device result).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat

    def build(ctr):
        src, dst, w = _SAMPLERS[spec.kind](jnp, spec, ctr)
        return _preprocess_device(src, dst, w, ctr,
                                  num_samples=spec.num_samples, cap=cap,
                                  scale=spec.scale)

    if mesh is None:
        return jax.jit(build)

    num_shards = int(np.prod(mesh.devices.shape))
    block = cap // num_shards

    def build_shard(ctr):
        s, d, k, cnt = build(ctr)
        i = jax.lax.axis_index("x") * block
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i, block)
        return sl(s), sl(d), sl(k), cnt

    fn = compat.shard_map(
        build_shard, mesh,
        in_specs=(P(),), out_specs=(P("x"), P("x"), P("x"), P()))
    return jax.jit(fn)


def build(spec: GraphSpec, mesh=None) -> DeviceEdges:
    """Generate + preprocess ``spec`` entirely on device.

    Returns canonical edges in engine layout (sharded along ``"x"`` when a
    mesh is given).  The only blocking transfer is the deduped edge count —
    one scalar, metered by the caller's benchmark harness, not an edge
    round-trip.  For the same spec the result is byte-identical to
    :func:`build_host` at any shard count.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    cap = _capacity(spec, num_shards)
    with jax.enable_x64():
        ctr = np.arange(cap, dtype=np.uint64)
        if mesh is not None:
            ctr = jax.device_put(ctr, NamedSharding(mesh, P()))
        src, dst, key, count = _build_fn(spec, cap, mesh)(ctr)
        num_edges = int(count)              # the build's single host sync
    return DeviceEdges(num_vertices=spec.num_vertices, num_edges=num_edges,
                       src=src, dst=dst, key=key, mesh=mesh, spec=spec)

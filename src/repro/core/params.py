"""Algorithm parameters — names and defaults follow the paper §3.6."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class GHSParams:
    """Tunables of the distributed MST engines.

    Paper §3.6 defaults, with TPU-adaptation notes:
      * ``max_msg_size``       — capacity (in messages) of each per-destination
        aggregation bucket per superstep (paper: 10000 bytes).
      * ``sending_frequency``  — supersteps between bucket flushes.  In the BSP
        engine every superstep ends with one fused exchange, so the knob
        becomes how many local process passes run between exchanges.
      * ``check_frequency``    — supersteps between drains of the deferred
        ``Test`` queue (faithful engine) / rounds between edge compactions
        (optimized engine).  This is the paper's key contribution (C1).
      * ``empty_iter_cnt_to_break`` — how many CONSECUTIVE silent activity
        checks (global queue+in-flight census == 0) must be observed before
        the engine halts (paper §3.6).  Each superstep's psum silence check
        counts as one observation; any activity resets the streak.  Values
        > 1 add exactly ``empty_iter_cnt_to_break - 1`` confirmation
        supersteps after first silence and never change the forest (a
        silent engine has no in-flight messages left to revive it).
      * ``hash_table_factor``  — hash table slots per local edge (paper:
        5 * 11 / 13 ≈ 4.23).
      * ``queue_capacity``     — override for the faithful engine's message
        ring capacity (default: sized from the shard's adjacency so
        overflow is impossible on well-formed runs).  Small values exercise
        the ``ERR_QUEUE_OVERFLOW`` error path deterministically.
    """

    max_msg_size: int = 4096
    sending_frequency: int = 1
    check_frequency: int = 5
    empty_iter_cnt_to_break: int = 1
    hash_table_factor: float = 5 * 11 / 13
    queue_capacity: int = 0           # 0 = auto-size from the shard adjacency
    # Optimization toggles (Fig 2 ablation ladder).
    use_hashing: bool = True          # C2: hash edge lookup vs linear search
    relaxed_test_queue: bool = True   # C1: separate Test queue
    compress_messages: bool = True    # C3: bit-packed message words
    # Engine-runtime extras (beyond paper) — shared by BOTH engines.
    compaction: str = "pow2"          # 'none' | 'pow2' lazy edge compaction
    use_pallas: bool = False          # route segment-min through the Pallas kernel
    partitioner: str = "block"        # graph distribution (DESIGN.md §7):
                                      # 'block' — contiguous slots / vertex ids
                                      #   (today's layout)
                                      # 'hashed' — pseudo-random scatter
                                      # 'balanced' — degree/edge-balanced
                                      # Edges for the Borůvka engine, vertices
                                      # (via relabeling) for GHS; every choice
                                      # yields a bit-identical forest.
    round_loop: str = "device"        # 'device': fused lax.while_loop engine
                                      #   (≤ 1 host sync per check_frequency
                                      #   interval, both engines)
                                      # 'host': legacy per-round / per-superstep
                                      #   host loop
    collective: str = "pmin"          # cross-shard per-round reduction
                                      # (DESIGN.md §11):
                                      # 'pmin' — full-width lax.pmin over the
                                      #   replicated (n,) arrays (seed
                                      #   behavior)
                                      # 'compressed' — delta exchange: each
                                      #   shard ships only the entries it
                                      #   improved this round as packed
                                      #   (index, value) candidate lists on a
                                      #   ppermute ring, with a bit-identity
                                      #   lax.pmin fallback when a shard
                                      #   overflows the static cap.  Forests
                                      #   are bit-identical either way; bytes
                                      #   shrink with the active edge count.
    interval_pipeline: int = 1        # interval dispatch depth (DESIGN.md
                                      # §11): 1 double-buffers the device
                                      # round loops (interval k+1 is
                                      # dispatched before interval k's fused
                                      # scalar readback is consumed, hiding
                                      # host-sync latency); 0 is the
                                      # sequential dispatch→readback→decide
                                      # loop.  Forests are byte-identical
                                      # either way; legacy host loops are
                                      # always sequential.
    round_kernel: str = "xla"         # Borůvka round body (DESIGN.md §9):
                                      # 'xla' — per-edge scatter/gather chain
                                      #   (_one_round, the seed behavior)
                                      # 'pallas' — fused masked min-plus
                                      #   election (kernels/spmv_minplus) with
                                      #   n-scale recording/hooking and one
                                      #   collective per round; with
                                      #   use_pallas=True the election runs
                                      #   as a Pallas kernel (interpreted
                                      #   on the CPU), otherwise the
                                      #   scatter-free sort lowering.
                                      #   Bit-identical forests either way.
    # Batched solving knobs (DESIGN.md §8) — minimum_spanning_forests only.
    batch_bucket: str = "pow2"        # pack_batch shape-bucketing policy:
                                      # 'pow2' rounds (n, m) up to powers of
                                      #   two so mixed sizes share executables
                                      # 'exact' buckets identical shapes only
    batch_max_vertices: int = 0       # per-graph capacity bounds for the
    batch_max_edges: int = 0          # batched path; 0 = unlimited, otherwise
                                      # pack_batch REJECTS oversized graphs
                                      # (ValueError), never truncates them
    batch_check_frequency: int = 1    # rounds per batched interval.  The
                                      # batched loop trades differently from
                                      # the single-graph one: its readback
                                      # amortizes over the whole bucket while
                                      # per-interval contraction shrinks every
                                      # subsequent round, so SHORT intervals
                                      # win (single-graph check_frequency is
                                      # untouched)
    # Filter-Borůvka sampling hybrid (DESIGN.md §10) — method="filter_boruvka".
    filter_sample_rate: float = 0.15  # Bernoulli keep probability of the
                                      # counter-based edge sample (splitmix64
                                      # over canonical edge ids — deterministic
                                      # at any shard count).  0 disables the
                                      # sample solve entirely (the final solve
                                      # then sees every edge — the empty-sample
                                      # guarantee); ≥ 1 samples everything.
    filter_levels: int = 16           # threshold levels of the connectivity
                                      # probe: the cycle rule is evaluated
                                      # against fragment labels of the sampled
                                      # forest restricted to tree edges below
                                      # per-level key quantiles.  More levels
                                      # → sharper path-max bound → fewer
                                      # survivors; never affects correctness.
    filter_threshold: int = 0         # survivor-count bound that triggers the
                                      # single recursion (a second
                                      # sample→solve→filter pass over the
                                      # survivors).  0 = auto: 4·num_vertices.
    # Incremental updates (DESIGN.md §13) — core/incremental.apply_updates.
    update_levels: int = 0            # threshold levels of the incremental
                                      # cycle probe (anchor-forest labels per
                                      # key quantile, plus the packed max-key
                                      # bound).  More levels → fewer
                                      # candidates reach the final solve;
                                      # never affects correctness.
                                      # 0 = follow filter_levels.
    # Serving knobs (DESIGN.md §12) — launch/serve.py continuous batching.
    serve_lanes: int = 8              # dispatch batch size: a bucket queue
                                      # flushes when it holds this many
                                      # graphs (or its deadline expires);
                                      # part-full flushes dispatch at the
                                      # pow2-rounded OCCUPIED lane count
                                      # (ghost-padded up to it, capped
                                      # here), so a solo deadline flush
                                      # pays a width-1 solve, not a
                                      # full-width one — warmup traces
                                      # every such width per bucket shape
    serve_max_wait_ms: float = 50.0   # deadline: the oldest queued request
                                      # waits at most this long before its
                                      # bucket is flushed part-full
    serve_max_queue: int = 64         # per-bucket admission bound; submits
                                      # beyond it are shed with
                                      # QueueFullError (backpressure, never
                                      # silent drops)


DEFAULT_PARAMS = GHSParams()

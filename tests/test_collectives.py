"""Compressed sharded collectives + double-buffered intervals (DESIGN.md §11).

Three layers of guarantees:

* the ``pmin_compressed`` delta exchange is BIT-identical to ``lax.pmin``
  on 1/2/4/8 shards, including the adversarial corners (all-equal keys, a
  fully-converged zero-delta round, and cap overflow → the ``lax.cond``
  fallback to the dense reduction);
* every engine path (boruvka, filter_boruvka, batched) elects the exact
  same forest under ``collective="compressed"`` as under ``"pmin"``;
* ``interval_pipeline=1`` (double-buffered dispatch) produces
  byte-identical forests to the sequential loop and keeps the
  ``host_syncs == intervals + 1`` consumed-readback contract.

Shard sweeps run in subprocesses (device count is locked at jax init);
the wire-format / byte-model / knob-validation units run in-process so
the coverage gate sees :mod:`repro.sharding.collectives`.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_child(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# ---------------------------------------------------------------------------
# direct collective: pmin_compressed ≡ lax.pmin on 1/2/4/8 shards
# ---------------------------------------------------------------------------

def test_pmin_compressed_bit_identity_1_2_4_8_shards():
    """Random deltas, all-equal keys, zero-delta round, and cap overflow
    all reduce bit-identically to ``lax.pmin`` on every shard count, for
    both engine value dtypes (uint64 best keys, uint32 hook parents)."""
    out = run_child("""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, shard_map
from repro.sharding import collectives

N = 96
rows = []
for shards in (1, 2, 4, 8):
    mesh = make_mesh((shards,), ("x",))
    for dtype, inf in ((jnp.uint32, 2**32 - 1), (jnp.uint64, 2**64 - 1)):
        with jax.enable_x64():
            default = jnp.full((N,), inf, dtype)
            rng = np.random.default_rng(shards)
            def case(n_improved, equal=False):
                data = np.full((shards, N), inf, np.uint64)
                if n_improved:
                    idx = rng.choice(N, size=n_improved, replace=False)
                    vals = rng.integers(1, 1 << 30, size=n_improved,
                                        dtype=np.uint64)
                    for s in range(shards):
                        if equal:
                            data[s, idx] = vals
                        else:
                            take = rng.random(n_improved) < 0.7
                            data[s, idx[take]] = vals[take] + s
                return jnp.asarray(data).astype(dtype)

            def both(x, cap):
                def f(xs):
                    x1 = xs[0]
                    a = jax.lax.pmin(x1, "x")
                    b = collectives.pmin_compressed(
                        x1, "x", default=default, cap=cap,
                        num_shards=shards)
                    return a[None], b[None]
                a, b = shard_map(f, mesh, in_specs=(P("x"),),
                                 out_specs=(P("x"), P("x")))(x)
                return (np.asarray(jax.device_get(a)),
                        np.asarray(jax.device_get(b)))

            for name, x, cap in [
                ("random", case(16), 32),
                ("all_equal", case(16, equal=True), 32),
                ("zero_delta", case(0), 32),
                ("overflow_fallback", case(64), 8),
            ]:
                a, b = both(x, cap)
                rows.append(dict(shards=shards, dtype=str(dtype.__name__),
                                 case=name,
                                 ok=bool(np.array_equal(a, b))))
print(json.dumps(rows))
""")
    rows = json.loads(out.strip().splitlines()[-1])
    assert len(rows) == 4 * 2 * 4
    bad = [r for r in rows if not r["ok"]]
    assert not bad, bad


# ---------------------------------------------------------------------------
# engines: compressed ≡ pmin forests on 1/2/4/8 shards
# ---------------------------------------------------------------------------

def test_engines_compressed_vs_pmin_1_2_4_8_shards():
    """boruvka and filter_boruvka elect the exact Kruskal forest under
    both collectives on every shard count; the compressed multi-shard
    runs actually engage the delta exchange at least once (comm_history
    witnesses a "compressed" interval) and honor the sync contract."""
    out = run_child("""
import numpy as np, json
from repro.compat import make_mesh
from repro.core import generators, kruskal_ref
from repro.core.mst_api import minimum_spanning_forest
from repro.core.params import GHSParams

g = generators.generate("rmat", 9, seed=3)
want = kruskal_ref.kruskal(g).edge_mask
rows = []
for shards in (1, 2, 4, 8):
    mesh = make_mesh((shards,), ("x",)) if shards > 1 else None
    for method in ("boruvka", "filter_boruvka"):
        masks = {}
        for coll in ("pmin", "compressed"):
            res, st = minimum_spanning_forest(
                g, method=method,
                params=GHSParams(collective=coll, check_frequency=2),
                mesh=mesh)
            masks[coll] = np.asarray(res.edge_mask)
            # filter merges several sub-solve ledgers (one trailing sync
            # each), so its merged contract is the inequality form
            sync_ok = (st.host_syncs == st.intervals + 1
                       if method == "boruvka"
                       else st.host_syncs > st.intervals >= 1)
            row = dict(shards=shards, method=method, collective=coll,
                       ok=bool(np.array_equal(masks[coll], want)),
                       sync_ok=bool(sync_ok))
            if method == "boruvka":
                modes = [m for (m, c, r, b) in st.comm_history]
                row["engaged"] = "compressed" in modes
                row["bytes"] = st.comm_bytes
            rows.append(row)
        rows.append(dict(shards=shards, method=method, collective="both",
                         ok=bool(np.array_equal(masks["pmin"],
                                                masks["compressed"])),
                         sync_ok=True))
print(json.dumps(rows))
""")
    rows = json.loads(out.strip().splitlines()[-1])
    assert len(rows) == 4 * 2 * 3
    bad = [r for r in rows if not (r["ok"] and r["sync_ok"])]
    assert not bad, bad
    # the delta exchange must actually carry the reduction somewhere on
    # multi-shard boruvka runs (not just fall back / stay dense)
    engaged = [r for r in rows
               if r.get("collective") == "compressed" and r["shards"] > 1
               and r["method"] == "boruvka"]
    assert any(r["engaged"] for r in engaged), engaged
    for r in engaged:
        assert r["bytes"] > 0


def test_batched_compressed_knob_and_pipeline():
    """The batched serving path accepts the knobs and stays bit-identical
    to per-graph solves under every (collective, interval_pipeline)
    combination — it never shards, so the knobs must be inert."""
    sys.path.insert(0, SRC)
    from repro.core import generators, kruskal_ref
    from repro.core.mst_api import minimum_spanning_forests
    from repro.core.params import GHSParams

    graphs = [generators.generate("rmat", 6, seed=s) for s in (1, 2, 3)]
    want = [kruskal_ref.kruskal(g).edge_mask for g in graphs]
    for coll in ("pmin", "compressed"):
        for pipe in (0, 1):
            forests, st = minimum_spanning_forests(
                graphs, params=GHSParams(collective=coll,
                                         interval_pipeline=pipe))
            for f, w in zip(forests, want):
                assert np.array_equal(np.asarray(f.edge_mask), w), (coll,
                                                                    pipe)
            # one trailing sync per bucketed interval_loop (merge sums them)
            assert st.host_syncs > st.intervals >= 1


# ---------------------------------------------------------------------------
# double-buffered intervals: pipeline 0 ≡ pipeline 1
# ---------------------------------------------------------------------------

def test_double_buffering_byte_identical_forests():
    """interval_pipeline=1 overlaps dispatch k+1 with readback k; the
    forests must stay byte-identical to the sequential loop for all three
    engines, the consumed-readback ledger must satisfy
    ``host_syncs == intervals + 1`` at both depths, and the overlapped
    run must actually overlap (overlapped_syncs == intervals, one
    speculative trailing dispatch)."""
    out = run_child("""
import numpy as np, json
from repro.compat import make_mesh
from repro.core import generators, kruskal_ref
from repro.core.mst_api import minimum_spanning_forest
from repro.core.params import GHSParams

rows = []
mesh = make_mesh((4,), ("x",))
for method, scale in (("boruvka", 9), ("filter_boruvka", 9), ("ghs", 7)):
    g = generators.generate("rmat", scale, seed=5)
    want = kruskal_ref.kruskal(g).edge_mask
    masks = {}
    stats = {}
    for pipe in (0, 1):
        res, st = minimum_spanning_forest(
            g, method=method,
            params=GHSParams(interval_pipeline=pipe, collective="compressed",
                             check_frequency=2),
            mesh=mesh)
        masks[pipe] = np.asarray(res.edge_mask)
        stats[pipe] = st
    st0, st1 = stats[0], stats[1]
    def sync_ok(st):
        # filter merges sub-solve ledgers: inequality form (see above)
        if method == "filter_boruvka":
            return st.host_syncs > st.intervals >= 1
        return st.host_syncs == st.intervals + 1
    rows.append(dict(
        method=method,
        oracle=bool(np.array_equal(masks[1], want)),
        identical=bool(np.array_equal(masks[0], masks[1])),
        sync0=bool(sync_ok(st0)),
        sync1=bool(sync_ok(st1)),
        seq_no_overlap=bool(st0.overlapped_syncs == 0
                            and st0.speculative_intervals == 0),
        overlapped=bool(st1.overlapped_syncs == st1.intervals),
        speculative=st1.speculative_intervals))
print(json.dumps(rows))
""", devices=4)
    rows = json.loads(out.strip().splitlines()[-1])
    assert len(rows) == 3
    for r in rows:
        assert r["oracle"] and r["identical"], r
        assert r["sync0"] and r["sync1"], r
        assert r["seq_no_overlap"], r
        assert r["overlapped"], r
        assert r["speculative"] >= 1, r


# ---------------------------------------------------------------------------
# in-process units: wire format, byte model, knob validation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def _src_path():
    sys.path.insert(0, SRC)
    yield


def test_byte_models(_src_path):
    from repro.sharding import collectives

    # (P-1) ring steps, cap entries of (4-byte index + value lane) each
    assert collectives.compressed_bytes(cap=64, num_shards=4,
                                        value_bytes=8) == 3 * 64 * 12
    # dense all-reduce lower bound: 2 (P-1)/P · n · value lanes
    assert collectives.dense_bytes(4096, 8, 8) == 2 * 7 * 4096
    # a P=1 "exchange" is free on both models
    assert collectives.compressed_bytes(cap=64, num_shards=1,
                                        value_bytes=8) == 0
    assert collectives.dense_bytes(4096, 1, 8) == 0


def test_knob_validation(_src_path):
    from repro.core import runtime
    from repro.sharding import collectives

    assert runtime.resolve_collective("pmin") == "pmin"
    assert runtime.resolve_collective("compressed") == "compressed"
    with pytest.raises(ValueError, match="collective"):
        runtime.resolve_collective("gossip")
    assert collectives.resolve_collective("pmin") == "pmin"
    with pytest.raises(ValueError):
        collectives.resolve_collective("nope")
    assert runtime.resolve_interval_pipeline(0) == 0
    assert runtime.resolve_interval_pipeline(1) == 1
    with pytest.raises(ValueError, match="interval_pipeline"):
        runtime.resolve_interval_pipeline(2)


def test_pmin_compressed_single_shard_paths(_src_path):
    """Both the ring path and the overflow fallback lower and run on the
    real (single-device) test backend — shard-count-1 exchange is the
    identity, and a tiny cap routes through the dense fallback."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.sharding import collectives

    n = 32
    mesh = make_mesh((1,), ("x",))
    default = jnp.full((n,), jnp.uint32(0xFFFFFFFF), jnp.uint32)
    x = np.full((1, n), 0xFFFFFFFF, np.uint32)
    x[0, 3] = 7
    x[0, 21] = 9

    def run(cap):
        def f(xs):
            return collectives.pmin_compressed(
                xs[0], "x", default=default, cap=cap, num_shards=1)[None]
        return np.asarray(jax.device_get(
            shard_map(f, mesh, in_specs=(P("x"),),
                      out_specs=P("x"))(jnp.asarray(x))))[0]

    for cap in (8, 1):           # ring path; cap overflow → dense fallback
        got = run(cap)
        assert np.array_equal(got, x[0]), cap


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(_src_path, tmp_path, monkeypatch, from_env):
    """The persistent cache goes where JAX_COMPILATION_CACHE_DIR says and
    the code sets no other path; without it, to the fixed in-checkout
    directory.  The XLA flags stay untouched either way."""
    import jax
    from repro import platform as platform_lib

    before = jax.config.jax_compilation_cache_dir
    flags = os.environ.get("XLA_FLAGS")
    env_dir = str(tmp_path / "cache")
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = platform_lib.use_compile_cache()
        if from_env:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == platform_lib.CACHE_DIR
            assert os.path.basename(got) == ".jax_cache"
            assert os.path.isfile(os.path.join(
                os.path.dirname(got), "src", "repro", "platform.py"))
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.environ.get("XLA_FLAGS") == flags

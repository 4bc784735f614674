"""The program's own tracing: phase scopes on the round's device ops, host
spans in the interval loop, the engines and the service's flush, and the
service's queue-wait counter."""
import glob
import gzip
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import boruvka_dist, generators, mst_api
from repro.core.graph import PAD_VERTEX
from repro.core.params import GHSParams
from repro.launch.serve import MSTService

PHASES = ("election", "record", "merge", "census", "compact")
_PHASE = re.compile(r"(^|/)(vmap\()?(%s)\)?(/|$)" % "|".join(PHASES))
_OP = re.compile(r"=\s*[^=]*?\b(gather|scatter|sort)\(")


def _single_args(n=16, cap=64):
    return (jnp.arange(n, dtype=jnp.uint32), jnp.zeros(cap, bool),
            jnp.full(cap, PAD_VERTEX, jnp.int32),
            jnp.full(cap, PAD_VERTEX, jnp.int32),
            jnp.zeros(cap, jnp.uint64), jnp.zeros(cap, jnp.int32))


def _batch_args(b=2, n=16, cap=32):
    comp = jnp.broadcast_to(jnp.arange(n, dtype=jnp.uint32), (b, n))
    return (comp, jnp.zeros((b, cap), bool),
            jnp.full((b, cap), PAD_VERTEX, jnp.int32),
            jnp.full((b, cap), PAD_VERTEX, jnp.int32),
            jnp.zeros((b, cap), jnp.uint64), jnp.zeros((b, cap), jnp.int32))


def _lower_single():
    return boruvka_dist._build_interval_fn(None, False).lower(
        *_single_args(), 3)


def _lower_fused():
    comp, mask, src, dst, key, slot = _single_args()
    return boruvka_dist._build_interval_fn_fused(None, "scatter", None).lower(
        comp, mask, src, dst, key, slot, slot, 3)     # canonical endpoints


def _lower_batch(contract_bits, election):
    b = 2
    fn = boruvka_dist._build_batch_interval_fn(False, contract_bits, election)
    return fn.lower(*_batch_args(b), jnp.zeros(b, bool),
                    jnp.zeros(b, jnp.int32), 3)


def _lower_compact():
    comp, _, src, dst, key, slot = _single_args()
    return boruvka_dist._build_compact_fn(None, 8).lower(
        comp, src, dst, key, slot)


def _lower_batch_compact():
    comp, _, src, dst, key, slot = _batch_args()
    return boruvka_dist._build_batch_compact_fn(8).lower(
        comp, src, dst, key, slot)


@pytest.mark.parametrize("lower", [
    _lower_single,
    _lower_fused,
    lambda: _lower_batch((4, 5), "scatter"),
    lambda: _lower_batch((4, 5), "sort"),
    lambda: _lower_batch(None, "scatter"),
    _lower_compact,
    _lower_batch_compact,
], ids=["single", "fused", "batch_packed", "batch_sort", "batch_plain",
        "compact", "batch_compact"])
def test_round_ops_carry_a_phase_scope(lower):
    """Every gather, scatter (the segmented min included) and sort of the
    interval and compaction programs names its phase in its op name, which
    a device trace shows as the op's ``tf_op``."""
    with jax.enable_x64():
        text = lower().compile().as_text()
    ops = [line for line in text.splitlines() if _OP.search(line)]
    assert ops
    unscoped = []
    for line in ops:
        name = re.search(r'op_name="([^"]*)"', line)
        if name is None or not _PHASE.search(name.group(1)):
            unscoped.append(line.strip()[:160])
    assert not unscoped, unscoped


def test_segmented_min_is_scoped_as_election():
    with jax.enable_x64():
        text = _lower_single().compile().as_text()
    names = re.findall(r'op_name="([^"]*segment_min64[^"]*)"', text)
    assert names and all("/election/" in n for n in names)


def test_pointer_doubling_is_named_under_merge():
    """``union_find.pointer_double`` is a jit of its own, inlined under
    the caller's ``merge`` scope: a device trace names its gathers
    ``pointer_double/gather``."""
    with jax.enable_x64():
        text = _lower_single().compile().as_text()
    names = re.findall(r'op_name="([^"]*jit\(pointer_double\)[^"]*)"', text)
    assert any(n.endswith("/gather") for n in names)
    assert all("/merge/jit(pointer_double)" in n for n in names)


def test_single_interval_scatters_no_u64():
    """The election's segmented min runs as native uint32 scatter-min
    passes under the ``election`` scope: no scatter of the single-graph
    interval writes 64-bit words, which the chip only emulates."""
    with jax.enable_x64():
        text = _lower_single().compile().as_text()
    scatters = [line for line in text.splitlines()
                if re.search(r"=\s*[^=]*?\bscatter\(", line)]
    assert scatters
    wide = [line.strip()[:160] for line in scatters
            if re.search(r"=\s*\(?[us]64\[", line)]
    assert not wide, wide
    elect = [line for line in scatters if "segment_min64" in line]
    assert len(elect) == 2 and all(
        re.search(r"=\s*u32\[", line) and "/election/" in line
        for line in elect), elect
    gathers = [line for line in text.splitlines()
               if "segment_min64" in line
               and re.search(r"=\s*[^=]*?\bgather\(", line)]
    assert gathers and all("/election/" in line for line in gathers)


# -- host spans ---------------------------------------------------------------

def _traced(tmp_path, run):
    """Run ``run()`` under the profiler; return its result and the host
    spans (name, start us, end us, args) of this thread."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = run()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.trace.json.gz"))
    assert len(found) == 1, found
    with gzip.open(found[0], "rt") as f:
        events = json.load(f)["traceEvents"]
    mine = [e for e in events if e.get("ph") == "X"
            and e.get("name") == "test.run"]
    assert len(mine) == 1
    pid, tid = mine[0]["pid"], mine[0]["tid"]
    spans = [(e["name"], e["ts"], e["ts"] + e.get("dur", 0.0),
              e.get("args", {})) for e in events
             if e.get("ph") == "X" and e["pid"] == pid and e["tid"] == tid]
    return out, spans


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("pipeline", [0, 1])
def test_solve_spans_one_readback_per_interval(tmp_path, pipeline):
    graph = generators.generate("rmat", 7, avg_degree=8, seed=3)
    params = GHSParams(check_frequency=1, interval_pipeline=pipeline)
    mst_api.minimum_spanning_forest(graph, params=params)   # compile

    def run():
        with jax.profiler.TraceAnnotation("test.run"):
            return mst_api.minimum_spanning_forest(graph, params=params)

    (_, stats), spans = _traced(tmp_path, run)
    assert stats.intervals > 1
    assert len(_named(spans, "interval.readback")) == stats.intervals
    assert len(_named(spans, "interval.finish")) == stats.intervals
    assert len(_named(spans, "interval.dispatch")) == \
        stats.intervals + stats.speculative_intervals
    assert len(_named(spans, "msf.compact")) == stats.compactions
    for name in ("msf.stage", "msf.fetch", "msf.forest"):
        assert len(_named(spans, name)) == 1, name
    for compact in _named(spans, "msf.compact"):
        assert any(_inside(compact, f)
                   for f in _named(spans, "interval.finish"))


def _service_graphs():
    return [generators.generate("rmat", 4, avg_degree=4, seed=s)
            for s in (0, 2)]


def test_flush_spans_nest_and_carry_the_trigger(tmp_path):
    params = GHSParams(serve_lanes=2, serve_max_wait_ms=50.0,
                       serve_max_queue=4, batch_max_vertices=64,
                       batch_max_edges=256)
    svc = MSTService(params, clock=lambda: 0.0)
    graphs = _service_graphs()
    for width in (2, 1):                     # compile outside the trace
        for g in graphs[:width]:
            svc.submit(g)
        svc.drain()

    def run():
        with jax.profiler.TraceAnnotation("test.run"):
            futs = [svc.submit(g) for g in graphs]
            svc.poll(now=0.0)                # a full bucket: size flush
            futs.append(svc.submit(graphs[0]))
            svc.poll(now=1.0)                # past the deadline
            return futs

    futs, spans = _traced(tmp_path, run)
    assert all(f.done() for f in futs)
    flushes = _named(spans, "serve.flush")
    assert [f[3].get("trigger") for f in flushes] == ["size", "deadline"]
    assert [f[3].get("occupied") for f in flushes] == ["2", "1"]
    assert [f[3].get("lanes") for f in flushes] == ["2", "1"]
    for child in ("serve.pack", "serve.solve", "serve.complete"):
        found = _named(spans, child)
        assert len(found) == 2, child
        assert all(any(_inside(c, f) for f in flushes) for c in found)
    for name in ("msf.stage", "interval.readback", "msf.fetch",
                 "msf.forest"):
        assert all(any(_inside(s, solve)
                       for solve in _named(spans, "serve.solve"))
                   for s in _named(spans, name)), name


# -- queue wait ---------------------------------------------------------------

def test_queue_wait_sums_each_requests_wait_for_its_flush():
    t = [0.0]
    params = GHSParams(serve_lanes=3, serve_max_wait_ms=50.0,
                       serve_max_queue=6, batch_max_vertices=64,
                       batch_max_edges=256)
    svc = MSTService(params, clock=lambda: t[0])
    g0, g1 = _service_graphs()
    svc.submit(g0)
    t[0] = 0.02
    svc.submit(g1)
    assert svc.poll(now=0.05) == 1           # the deadline flush takes both
    assert svc.stats.completed == 2
    assert svc.stats.queue_wait_s == pytest.approx(0.08)
    # Without an injected time, a flush starts on the service's clock.
    t[0] = 0.1
    svc.submit(g0)
    t[0] = 0.5
    assert svc.drain() == 1
    assert svc.stats.queue_wait_s == pytest.approx(0.08 + 0.4)


def test_stats_no_longer_keep_an_active_history():
    graph = generators.generate("rmat", 6, avg_degree=8, seed=1)
    _, stats = mst_api.minimum_spanning_forest(graph)
    assert not hasattr(stats, "active_history")
    assert stats.edges_scanned > 0

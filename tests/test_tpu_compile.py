"""Compile the main path for a described TPU v5e chip, with no chip.

The TPU compiler is installed beside jax, and ``topologies`` describes a
v5e chip that is not attached: Mosaic then refuses a kernel exactly as on
the chip (unaligned slices, unsupported gathers, 64-bit scalars), and
``memory_analysis`` prices a program against the chip's 16 GB.  Shapes are
those of the smoke test's Graph500 graph (``chip_smoke.py``: rmat scale
22, 2**22 vertices, 2**26 edge slots; the election scans both endpoint
lanes, 2**27).  Nothing runs, so these tests say nothing about results or
times — the kernels' semantics are checked in interpret mode by
``test_kernels.py``.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

SCALE = 22
N = 1 << SCALE                  # vertices
CAP = 1 << (SCALE + 4)          # edge slots: edge factor 16, pow2 capacity
LANES2 = 2 * CAP                # both endpoint lanes of the election
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch, no_cache):
    """Engine code resolves ``interpret`` from the default backend, which
    is the CPU here: steer it to the compiled kernels for these tests, and
    drop traces made with the interpreter."""
    from repro.kernels.segment_min import segment_min
    monkeypatch.setattr(segment_min, "interpret_mode",
                        lambda interpret=None: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    with jax.enable_x64():
        return jax.jit(fn).lower(*args).compile()


def _kernels():
    from repro.kernels.segment_min.segment_min import (
        segmented_min2_scan, segmented_min_scan)
    from repro.kernels.spmv_minplus.spmv_minplus import masked_minplus_scan
    u32, i32 = jnp.uint32, jnp.int32
    return {
        "segmented_min2_scan": (
            lambda s, h, l: segmented_min2_scan(s, h, l, interpret=False),
            (i32, u32, u32)),
        "segmented_min_scan": (
            lambda s, v: segmented_min_scan(s, v, interpret=False),
            (i32, u32)),
        "masked_minplus_scan": (
            lambda s, o, h, l: masked_minplus_scan(s, o, h, l,
                                                   interpret=False),
            (i32, i32, u32, u32)),
    }


@pytest.mark.parametrize("name", ["segmented_min2_scan",
                                  "segmented_min_scan",
                                  "masked_minplus_scan"])
def test_scan_kernel_compiles_for_v5e(name, one_chip, no_cache):
    fn, dtypes = _kernels()[name]
    args = [_sds(one_chip, (LANES2,), d) for d in dtypes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == LANES2 * 4 * len(dtypes)


def _interval_args(one_chip, fused: bool):
    s = lambda shape, d: _sds(one_chip, shape, d)       # noqa: E731
    edges = [s((CAP,), jnp.int32), s((CAP,), jnp.int32),
             s((CAP,), jnp.uint64)]
    comp = s((N,), jnp.uint32)
    rounds = s((), jnp.int32)
    if fused:   # canonical-eid bitmap + canonical endpoints, replicated
        return [comp, s((CAP,), jnp.bool_), *edges, s((CAP,), jnp.int32),
                s((CAP,), jnp.int32), rounds]
    return [comp, s((CAP,), jnp.bool_), *edges, s((CAP,), jnp.int32),
            rounds]


def _fits_chip(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    return total


def test_boruvka_interval_compiles_for_v5e(one_chip, no_cache):
    """The default round (XLA body over uint64 packed keys: scatter-min,
    gathers, hooking, pointer doubling) lowers on XLA:TPU and fits one
    chip at scale 22."""
    from repro.core import boruvka_dist
    fn = boruvka_dist._build_interval_fn(None, False)
    with jax.enable_x64():
        compiled = fn.lower(*_interval_args(one_chip, False)).compile()
    _fits_chip(compiled)
    # Donated state (comp, mask) aliases its outputs.
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_fused_pallas_interval_compiles_for_v5e(one_chip, compiled_kernels):
    """``round_kernel="pallas", use_pallas=True``: the masked min-plus
    election is a compiled Mosaic kernel inside the interval program."""
    from repro.core import boruvka_dist
    fn = boruvka_dist._build_interval_fn_fused(None, "pallas", None)
    with jax.enable_x64():
        compiled = fn.lower(*_interval_args(one_chip, True)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_chip(compiled)

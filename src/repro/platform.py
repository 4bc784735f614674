"""Process-level JAX set-up: backend pinning and the compile cache.

* :func:`set_platform` — force the jax platform (``cpu``/``tpu``).  It sets
  no XLA flags: an unknown flag in ``XLA_FLAGS`` aborts the process at
  backend start-up.
* :func:`force_host_device_count` — emulate an N-device host (the
  ``--xla_force_host_platform_device_count`` flag the multi-shard tests
  and CPU sweeps rely on).
* :func:`set_debug_nan` / :func:`set_x64` — debugging & width toggles.
* :func:`pin` — one-stop shop used by ``benchmarks/common.py``.
* :func:`use_compile_cache` — JAX's persistent compilation cache, placed
  from outside (``JAX_COMPILATION_CACHE_DIR``) or at a fixed path inside
  the checkout.

The pinning helpers must run BEFORE jax initializes its backends; each
raises if called too late rather than silently doing nothing.
"""
from __future__ import annotations

import os

# Fixed cache path inside the checkout (git-ignored).  The path is part of
# what a later run must find again, so it never depends on the process,
# the time or a temporary name.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def _jax_initialized() -> bool:
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:       # private API moved — assume the worst
        return True


def _require_uninitialized(what: str) -> None:
    if _jax_initialized():
        raise RuntimeError(
            f"{what} must be set before jax initializes its backends; "
            "call repro.platform helpers at process start (see "
            "benchmarks/common.py)")


def _append_xla_flags(flag: str) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if flag in flags.split():
        return
    os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()


def set_platform(platform: str = "cpu") -> None:
    """Force the jax platform."""
    if platform not in ("cpu", "tpu"):
        raise ValueError(f"unknown platform {platform!r}")
    _require_uninitialized("platform")
    import jax
    jax.config.update("jax_platform_name", platform)


def force_host_device_count(n: int) -> None:
    """Emulate ``n`` host devices (CPU shard sweeps / multi-device CI)."""
    if n < 1:
        raise ValueError("device count must be >= 1")
    _require_uninitialized("host device count")
    _append_xla_flags(f"--xla_force_host_platform_device_count={n}")


def set_x64(enable: bool = True) -> None:
    """Global x64 default.  The engines scope their own ``jax.enable_x64``
    contexts, so benches normally leave this alone; kernels-only legs that
    feed uint64 keys straight into ops use it."""
    import jax
    jax.config.update("jax_enable_x64", enable)


def set_debug_nan(enable: bool = True) -> None:
    import jax
    jax.config.update("jax_debug_nans", enable)


def pin(platform: str | None = None, host_devices: int | None = None,
        x64: bool | None = None, debug_nan: bool | None = None) -> None:
    """Apply every requested pin in the order that keeps them legal
    (env-var flags before any jax.config touch can initialize a backend)."""
    if host_devices is not None:
        force_host_device_count(host_devices)
    if platform is not None:
        set_platform(platform)
    if x64 is not None:
        set_x64(x64)
    if debug_nan is not None:
        set_debug_nan(debug_nan)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken it
    as ``jax_compilation_cache_dir`` and nothing is changed here.
    Otherwise the cache goes to :data:`CACHE_DIR`, ``.jax_cache/`` at the
    root of the checkout."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR

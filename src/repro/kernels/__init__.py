"""Pallas kernels of the MST round body (``segment_min``, ``spmv_minplus``).

:func:`interpret_mode` is the one place that decides how a kernel runs:
interpreted on the CPU (tests and CPU runs check the exact kernel
semantics there), compiled by Mosaic on the TPU.  Kernel entries take
``interpret=None`` and resolve it here; a test may pass a bool to pin it.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: "bool | None" = None) -> bool:
    """``interpret`` if given, else True exactly on the CPU backend."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"

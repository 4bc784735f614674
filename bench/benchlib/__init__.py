"""The benchmark's own library: finding cells by name, the load generator,
the reference, trace reduction, compile metering and the peaks table.

Nothing here imports JAX or the program at module import time: the
program under test (``src/repro``) is imported by the drivers and by
``harness`` only once a run has found its device.
"""

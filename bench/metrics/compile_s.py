"""Seconds of XLA backend compilation during set-up (JAX monitoring
events; programs loaded from the persistent cache do not compile)."""


def read(rec):
    return rec.setup_compile_s

"""Percent of the solve window in which no operation ran on the device:
1 - busy / window, from the trace."""


def read(rec):
    return None if rec.trace is None else 100.0 * rec.trace.idle_share

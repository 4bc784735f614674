"""Percent of the device's busy time spent in XLA scatter operations (the
election's segment-min and the hook's parent writes), from the trace."""


def read(rec):
    t = rec.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.scatter_s / t.busy_s

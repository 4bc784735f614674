"""Percent of the device's op time spent in pointer doubling, the hook's
shortcut (``union_find.pointer_double``): ops whose kind, from the
trace, starts with ``pointer_double/``.  A program whose trace names no
such op reads nothing."""

PREFIX = "pointer_double/"


def read(rec):
    t = rec.trace
    if t is None:
        return None
    total = sum(t.op_s.values())
    mine = [s for kind, s in t.op_s.items() if kind.startswith(PREFIX)]
    if not mine or total <= 0:
        return None
    return 100.0 * sum(mine) / total

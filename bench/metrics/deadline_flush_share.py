"""Percent of the window's service flushes that its deadline
(``serve_max_wait_ms``) triggered, from ``ServeStats`` counters."""


def read(rec):
    c = rec.window.counters
    total = sum(c.get(k, 0) for k in
                ("size_flushes", "deadline_flushes", "drain_flushes"))
    if not total:
        return None
    return 100.0 * c.get("deadline_flushes", 0) / total

"""95th percentile of how late the load generator submitted each request
behind its schedule (the driver's thread also runs the service's flushes,
so this is the host cost of the serve path as the generator feels it)."""
from benchlib import stats


def read(rec):
    lag = rec.window.samples.get("generator_lag_ms")
    return stats.percentile(lag, 95) if lag else None

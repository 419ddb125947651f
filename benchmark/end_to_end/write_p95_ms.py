"""95th percentile, call of ``propose`` to return of the client's ``get``,
over every write acknowledged inside the window."""

from benchmark import stats


def read(window):
    sample = window.latencies_ms("write")
    return stats.percentile(sample, 0.95) if sample else None

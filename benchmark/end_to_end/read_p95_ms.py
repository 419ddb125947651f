"""95th percentile, call of ``read_index`` to return of the lookup that
follows the client's ``get``, over every read served inside the window."""

from benchmark import stats


def read(window):
    sample = window.latencies_ms("read")
    return stats.percentile(sample, 0.95) if sample else None

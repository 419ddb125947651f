"""client API: 95th percentile, call of ``propose`` to return of the client's
``get``, over the writes acknowledged in the traced run's window.  Where the
cell holds ``write_p95_ms`` end to end this restates it under tracing; where
the tail is too quantized in engine rounds to be held to a bound
(``upstream-48.write16``: 768 writes in flight, PERF.md section 2) this is
where it is read.  In a closed loop, operations in flight = rate x latency,
so it moves with ``acked_ops_per_s``."""

from benchmark import stats


def read(run):
    if not run.write_latencies_ms:
        return None
    return stats.percentile(run.write_latencies_ms, 0.95)

"""client API: mean time of one ``NodeHost.start_replica`` call
(``nodehost_start_replica_us{phase=total}``).  Cumulative since the process
started, read at the window's end: set-up is over before the window opens."""

from benchmark.window_registry import key, ratio


def read(run):
    after = run.registry_after
    return ratio(
        after.get(key("nodehost_start_replica_us", "sum", phase="total")),
        after.get(key("nodehost_start_replica_us", "count", phase="total")),
        1e-3)

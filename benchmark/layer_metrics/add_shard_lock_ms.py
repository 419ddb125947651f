"""client API: mean wait of one ``add_shard`` call for the engine lock
(``engine_add_shard_lock_us``): the part of ``start_replica_ms`` a caller
spends behind the engine's rounds, lane injections of earlier calls
included.  Cumulative, read at the window's end: set-up precedes the window."""

from benchmark.window_registry import key, ratio


def read(run):
    after = run.registry_after
    return ratio(after.get(key("engine_add_shard_lock_us", "sum")),
                 after.get(key("engine_add_shard_lock_us", "count")), 1e-3)

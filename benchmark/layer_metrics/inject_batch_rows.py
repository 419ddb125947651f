"""client API: replicas one ``_flush_injections`` batch wrote into the device
state (``engine_inject_rows`` over the batches ``engine_inject_flush_us``
counted): above 1 where admissions queue between rounds and share one
``inject_rows`` program.  Cumulative, read at the window's end: set-up
precedes the window."""

from benchmark.window_registry import key, ratio


def read(run):
    after = run.registry_after
    return ratio(after.get("engine_inject_rows"),
                 after.get(key("engine_inject_flush_us", "count")))

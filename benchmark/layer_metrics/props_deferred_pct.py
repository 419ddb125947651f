"""client API: share of the proposals a staging pass looked at in the window
that it put back for want of a prop slot (``engine_props_deferred`` over
deferred plus ``engine_props_staged``): a proposal put back n times counts n
times, so this is the share of staging work that admitted nothing."""

from benchmark.window_registry import delta, ratio


def read(run):
    deferred = delta(run, "engine_props_deferred")
    staged = delta(run, "engine_props_staged")
    if deferred is None or staged is None:
        return None
    return ratio(deferred, deferred + staged, 100.0)

"""engine step loop: of the lanes the engines' output passes retired in the
window (``engine_retire_named``), the share the round's program named in the
download's ``active`` column, in percent; the rest are rows only the host
added (they staged proposals and came back with nothing else to do).  A
falling share says the host finds its candidates for itself again."""

from benchmark.window_registry import delta, key, ratio


def read(run):
    device = delta(run, key("engine_retire_named", by="device"))
    host = delta(run, key("engine_retire_named", by="host"))
    if device is None or host is None:
        return None
    return ratio(device, device + host, 100.0)

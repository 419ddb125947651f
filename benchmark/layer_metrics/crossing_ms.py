"""engine step loop: mean host time inside one sanctioned host<->device
crossing (``device_crossing_us``, every tag) over the window.  A download
blocks until the device has the value, so a round's ``upload`` and ``fetch``
are about ``round_crossings`` times this."""

from benchmark.window_registry import delta_over_labels, ratio


def read(run):
    return ratio(delta_over_labels(run, "device_crossing_us", "sum"),
                 delta_over_labels(run, "device_crossing_us", "count"), 1e-3)

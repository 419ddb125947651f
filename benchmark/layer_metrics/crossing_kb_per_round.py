"""engine step loop: bytes that crossed between host and device per engine
round, in KB of 1,000 bytes: the window's growth of
``device_crossing_bytes`` over every tag (``capacity.METER``: the ``nbytes``
of every sanctioned upload and download, shape-derived) over the rounds
recorded in it (``engine_round_us{phase=total}``).  By the shapes ~2.4 MB
at 1,024 lanes an engine (``[G, 231]`` up, ``[G, 345]`` down, int32) and
~9.4 MB at 4,096: what a sparse upload and a compacted download are judged
by.  None where the program counts no bytes."""

from benchmark.window_registry import delta, delta_over_labels, key, ratio


def read(run):
    return ratio(delta_over_labels(run, "device_crossing_bytes"),
                 delta(run, key("engine_round_us", "count", phase="total")),
                 1e-3)

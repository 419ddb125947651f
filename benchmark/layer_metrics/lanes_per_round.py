"""engine step loop: lanes an engine round's output pass took into its two
per-lane loops (``engine_round_lanes{what=processed}``), a round, over the
rounds the window's engines recorded (``engine_round_us{phase=total}``): how
much of the ``[G]`` axis a round's host work covers."""

from benchmark.window_registry import delta, key, ratio


def read(run):
    return ratio(delta(run, key("engine_round_lanes", what="processed")),
                 delta(run, key("engine_round_us", "count", phase="total")))

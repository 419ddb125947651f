"""apply: median time of a sampled write in lifecycle dwells ``apply_queue``
+ ``apply`` (hand-off to the apply pool, state machine update run)."""

from benchmark.layers import dwell_ms


def read(run):
    return dwell_ms(run, "proposal", ("apply_queue", "apply"))

"""engine step loop: median time of a sampled write in lifecycle dwells
``dispatch`` + ``retire`` (staging build to the step's outputs in hand)."""

from benchmark.layers import dwell_ms


def read(run):
    return dwell_ms(run, "proposal", ("dispatch", "retire"))

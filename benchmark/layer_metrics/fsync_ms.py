"""durability: median time of a sampled write in lifecycle dwells ``save`` +
``fsync`` (update batch assembled, durable flush done)."""

from benchmark.layers import dwell_ms


def read(run):
    return dwell_ms(run, "proposal", ("save", "fsync"))

"""read path: median time of a sampled ReadIndex from enqueue to the quorum
round confirming its index (lifecycle read-span dwell ``read_quorum``)."""

from benchmark.layers import dwell_ms


def read(run):
    return dwell_ms(run, "read", ("read_quorum",))

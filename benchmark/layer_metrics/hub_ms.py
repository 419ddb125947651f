"""transport: median time of a sampled write in lifecycle dwells ``hub_send``
+ ``hub_recv`` + ``ack_return``.  A commit on resident mesh links carries
none of these stamps, so there this reads nothing."""

from benchmark.layers import dwell_ms


def read(run):
    return dwell_ms(run, "proposal", ("hub_send", "hub_recv", "ack_return"))

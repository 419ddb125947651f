"""client API: median wait of a sampled write from ``propose`` enqueue to the
engine's staging build (lifecycle dwell ``stage``)."""

from benchmark.layers import dwell_ms


def read(run):
    return dwell_ms(run, "proposal", ("stage",))

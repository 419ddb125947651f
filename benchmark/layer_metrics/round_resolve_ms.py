"""transport: mean of a round's ``resolve`` phase: proposal fates, building the
round's messages and updates, and both send loops into the hub."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "resolve")

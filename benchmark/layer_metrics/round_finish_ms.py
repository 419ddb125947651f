"""apply: mean of a round's ``finish`` phase: commit cache, read completions,
the apply hand-off, leader edges and events; the fleet and health
collection when its countdown fires."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "finish")

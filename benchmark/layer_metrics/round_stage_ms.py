"""engine step loop: mean of a round's ``stage`` phase: lane injections, the
dirty swap, the per-lane staging loop, the tick write, removals."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "stage")

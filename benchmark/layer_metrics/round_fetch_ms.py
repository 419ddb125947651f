"""engine step loop: mean of a round's ``fetch`` phase: the flag-matrix
download, the output pulls that build the activity mask and the gather of
the saved rows' term ring; the phase in which the host waits for the device."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "fetch")

"""engine step loop: mean of a round's ``upload`` phase: the inbox and input
uploads and the jitted step entry's return (the ``kernel_engine.step``
annotation)."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "upload")

"""engine step loop: mean host time of an engine round, ``stage`` to ``finish``
(``engine_round_us{phase=total}``, the round timer of ``tracing.py``), over
the rounds of all engines recorded in the window.  A round that found
nothing to do is not one."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "total")

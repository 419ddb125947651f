"""engine step loop: mean time between two recorded rounds of one engine: from
the end of a round to ``step_all`` holding the engine lock again (worker
wake-up, host-node steps, idle passes, the lock).  ``1000 / (round_ms +
round_wait_ms)`` is an engine's rounds per second."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "wait")

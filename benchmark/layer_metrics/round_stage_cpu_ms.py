"""engine step loop: mean thread CPU time of the engine thread in a round's
``stage`` phase: admissions, injections, the dirty swap, the per-lane staging
loop, the tick write.  ``round_stage_ms`` less this is what the thread spent
blocked there.

The round timer reads the CPU clock at the phase boundaries in some rounds
only (about one an engine per 40 ms, drawn on the round's number and the
engine's mean round, so a long round is as likely to be read as the one after
it: the read is a 5.6 us system call on the chip's host) and at a round's two
ends in every round.  So a phase's mean is its SHARE of the CPU time of the
rounds that read it (``engine_round_phase_cpu_us.sum{phase}`` over the six
phases' sum) times the mean CPU time of all rounds (``engine_round_cpu_us`` over
the rounds of ``engine_round_us{phase=total}``).  The six therefore add up to
``round_oncpu_pct`` x ``round_ms`` / 100 by construction (shares of one whole):
that sum checks nothing; the shares are as good as the draw is even."""

from benchmark.window_registry import delta, key, ratio

PHASES = ("stage", "upload", "fetch", "resolve", "save", "finish")


def phase_cpu_ms(run, phase: str):
    read_us = {p: delta(run, key("engine_round_phase_cpu_us", "sum", phase=p))
               for p in PHASES}
    if None in read_us.values():
        return None
    share = ratio(read_us[phase], sum(read_us.values()))
    round_cpu_us = ratio(
        delta(run, key("engine_round_cpu_us", "sum")),
        delta(run, key("engine_round_us", "count", phase="total")))
    if share is None or round_cpu_us is None:
        return None
    return share * round_cpu_us / 1e3


def read(run):
    return phase_cpu_ms(run, "stage")

"""apply: mean a round of the part of ``finish`` that applies
(``engine_round_part_us.sum{part=finish.apply}``): over the rows with an apply
window, the walk of the payload mirror and the state machine's ``handle`` with
its apply stamps."""

from benchmark.window_registry import delta, key, ratio


def part_ms(run, name: str):
    """Mean host time of part ``name`` of a round (``tracing.ROUND_PARTS``)
    over the rounds the window's engines recorded: the round timer sums a
    part over every committed round, which ``engine_round_us{phase=total}``
    counts.  (``key`` cannot spell a label named ``part``: it is the name of
    its own second parameter.)"""
    return ratio(delta(run, f"engine_round_part_us.sum{{part={name}}}"),
                 delta(run, key("engine_round_us", "count", phase="total")),
                 1e-3)


def read(run):
    return part_ms(run, "finish.apply")

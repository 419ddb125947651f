"""engine step loop: mean a round of the every-tenth-round collection inside
``finish`` (``engine_round_part_us.sum{part=finish.collect}``): the fleet
statistics, the health triage and the invariant probe as ONE program, one
array down and one array carried, then the capacity snapshot; 0 in nine rounds
of ten.  A program older than the part reads nothing: compare with its
``round_finish_ms`` less ``finish_apply_ms`` and ``finish_ack_ms``."""

from benchmark.layer_metrics.finish_apply_ms import part_ms


def read(run):
    return part_ms(run, "finish.collect")

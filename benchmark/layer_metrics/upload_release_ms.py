"""engine step loop: mean a round of the rebinding of the resident state
after the jitted entry returned
(``engine_round_part_us.sum{part=upload.release}``): where the previous round's
three resident arrays die, each destructor a point where jaxlib lets the
interpreter go and has to take it back."""

from benchmark.layer_metrics.finish_apply_ms import part_ms


def read(run):
    return part_ms(run, "upload.release")

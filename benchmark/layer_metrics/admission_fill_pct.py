"""client API: proposals given a prop slot in the window over the slots offered
(``kernel_proposal_cap`` for every row that staged at least one proposal in a
round): how full the rounds' admission was where it was used."""

from benchmark.window_registry import delta, ratio


def read(run):
    return ratio(delta(run, "engine_props_staged"),
                 delta(run, "engine_prop_slots_offered"), 100.0)

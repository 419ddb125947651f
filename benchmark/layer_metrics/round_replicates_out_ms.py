"""transport: mean, over the rounds that sent any, of the time from a round's
start to the return of the send of its REPLICATEs
(``engine_round_mark_us{mark=replicates_out}``): how long a leader's round
holds a write before its followers can hear of it."""

from benchmark.window_registry import mean_ms


def read(run):
    return mean_ms(run, "engine_round_mark_us", mark="replicates_out")

"""transport: mean, over the rounds that sent any, of the time from a round's
start to the return of the send of everything but REPLICATEs
(``engine_round_mark_us{mark=responses_out}``; after the save, so a follower's
acknowledgement leaves once its entries are durable): how long a follower's
round holds a write before its leader can count it."""

from benchmark.window_registry import mean_ms


def read(run):
    return mean_ms(run, "engine_round_mark_us", mark="responses_out")

"""durability: mean of a round's ``save`` phase: the round's
``save_raft_state`` calls, fsync included."""

from benchmark.window_registry import round_phase_ms


def read(run):
    return round_phase_ms(run, "save")

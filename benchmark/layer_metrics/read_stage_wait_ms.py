"""read path: mean wait of a staged ReadIndex context for its staging, from
the enqueue of its batch's first read (a forwarded read: from its arrival at
the leader's host) to the engine round that stages it
(``read_stage_wait_us``).  What is left of ``read_quorum_ms`` is the quorum
round itself."""

from benchmark.window_registry import mean_ms


def read(run):
    return mean_ms(run, "read_stage_wait_us")

"""Acknowledged writes (quorum-committed, fsynced, applied) plus served
linearizable reads whose reply arrived inside the window, per second of it."""

from benchmark import stats


def read(window):
    return stats.per_second(len(window.acked), window.seconds)

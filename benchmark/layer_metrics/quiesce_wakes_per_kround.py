"""kernel: lanes that left quiesce in the window per 1,000 engine rounds:
the window's growth of ``engine_quiesce_wakes`` (at every fleet digest, the
growth of the resident ``quiesce_epoch`` column summed over an engine's
occupied lanes) over the rounds the window's engines recorded
(``engine_round_us{phase=total}``).  0 in a sound window: nothing is sent to
an idle group, so a wake there is an election, a stray message or a
heartbeat that should not have been sent."""

from benchmark.window_registry import delta, key, ratio


def read(run):
    return ratio(delta(run, "engine_quiesce_wakes"),
                 delta(run, key("engine_round_us", "count", phase="total")),
                 1000.0)

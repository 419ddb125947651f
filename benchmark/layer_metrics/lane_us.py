"""engine step loop: host time of a round's per-lane phases (``resolve``,
``save``, ``finish`` of ``engine_round_us``) over the lanes those phases
worked through (``engine_round_lanes{what=processed}``), in the window: what
one live lane costs an engine thread a round."""

from benchmark.window_registry import delta, key, ratio

PHASES = ("resolve", "save", "finish")


def read(run):
    spent = [delta(run, key("engine_round_us", "sum", phase=p))
             for p in PHASES]
    if any(us is None for us in spent):
        return None
    return ratio(sum(spent),
                 delta(run, key("engine_round_lanes", what="processed")))

"""engine step loop: of the lanes the engines' output passes retired in the
window (``engine_retire_lanes``), the share retired by columns of the round's
download and not through the per-lane handler of a rare class (a witness
snapshot, a ReadIndex completion or drop, a config change, an escalation, a
save window wider than the download's), in percent: how often the columnar
pass engages."""

from benchmark.window_registry import delta, key, ratio


def read(run):
    columnar = delta(run, key("engine_retire_lanes", path="columnar"))
    per_lane = delta(run, key("engine_retire_lanes", path="per_lane"))
    if columnar is None or per_lane is None:
        return None
    return ratio(columnar, columnar + per_lane, 100.0)

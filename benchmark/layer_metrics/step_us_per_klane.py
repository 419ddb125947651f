"""kernel: device time of the step program per call and per chip
(``step_kernel_us``) for every 1,000 rows the step ran over: the engine's
capacity, which each engine states in its gauge
``engine_lanes{what=capacity,engine=...}`` (the step runs over every row an
engine holds, live or not; a closed engine reads 0).  Whether the step is
linear in rows: 1,437 at 1,024 rows (``fleet-1k.write16-hot96``), read
beside it at 4,096 (``fleet-4k.write16-hot96``).  None where the program
has no such gauge, or where engines of several heights stand behind the one
step time."""

from benchmark.window_registry import key, ratio

GAUGE = key("engine_lanes", what="capacity")[:-1] + ","


def read(run):
    prog = run.step_program()
    if prog is None or prog["calls"] <= 0:
        return None
    heights = {rows for k, rows in run.registry_after.items()
               if k.startswith(GAUGE) and rows}
    if len(heights) != 1:
        return None
    return ratio(prog["seconds"] * 1e6 / prog["calls"], heights.pop(), 1000.0)

"""kernel: device time of the step program per call and per chip, from the
profiler's program line."""


def read(run):
    prog = run.step_program()
    if prog is None or prog["calls"] <= 0:
        return None
    return prog["seconds"] * 1e6 / prog["calls"]

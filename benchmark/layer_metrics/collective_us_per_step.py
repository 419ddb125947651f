"""mesh engine: device time of collective operations (all-gather,
all-reduce, ...) in the capture, summed over the chips, per engine step
(0 where the captured steps ran none)."""


def read(run):
    prog = run.step_program()
    if prog is None:
        return None
    return run.capture["collective_s"] * 1e6 / prog["steps"]

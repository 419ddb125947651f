"""mesh engine: device time of the step's exchange (its all-gathers and
all-reduce) per step on ONE chip that ran the step: the capture's
collective time over the calls of the step program, one call a chip a
step.  ``collective_us_per_step`` sums the chips; this is the figure
``exchange_roofline`` divides by."""


def read(run):
    prog = run.step_program()
    if (prog is None or prog["calls"] <= 0 or "mesh" not in run.config
            or run.capture["collective_s"] <= 0):
        return None
    return run.capture["collective_s"] * 1e6 / prog["calls"]

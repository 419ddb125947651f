"""kernel: the least time a chip could take for one step (bytes of the
step's arguments and outputs on one chip over the HBM peak; the step is
memory-bound) as a share of the step program's measured device time."""

from benchmark import peaks


def read(run):
    prog = run.step_program()
    if prog is None or prog["calls"] <= 0 or not run.step_bytes:
        return None
    least_s = peaks.least_step_seconds(run.step_bytes, run.device_kind)
    return 100.0 * least_s / (prog["seconds"] / prog["calls"])

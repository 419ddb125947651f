"""device: share of the captured window in which no operation ran, averaged
over the chips the cell asks for."""


def read(run):
    if run.capture is None or run.capture["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.capture["busy_s"] / run.capture["window_s"])

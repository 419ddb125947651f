"""engine step loop: calls of the step entries (``capacity.TRACKER``) inside
the window, per engine, per second."""

from benchmark import stats


def read(run):
    return stats.steps_per_second(
        run.step_calls(run.tracker_before), run.step_calls(run.tracker_after),
        run.engines, run.window_s)

"""engine step loop: sanctioned host<->device crossings per engine round: the
window's observations of ``device_crossing_us`` over every tag
(``capacity._SanctionedCrossing``: ``inbox_up``, ``input_up``, ``output_flags``,
``lazy_out``, ``lt_rows``, ...) over the rounds recorded in it
(``engine_round_us{phase=total}``)."""

from benchmark.window_registry import delta, delta_over_labels, key, ratio


def read(run):
    return ratio(delta_over_labels(run, "device_crossing_us", "count"),
                 delta(run, key("engine_round_us", "count", phase="total")))

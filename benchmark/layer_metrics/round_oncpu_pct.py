"""engine step loop: share of the rounds' host time that the engine thread spent
on the CPU (``time.thread_time_ns`` over the round, ``engine_round_cpu_us``)
rather than blocked: on the device, the disk or the interpreter lock."""

from benchmark.window_registry import delta, key, ratio


def read(run):
    return ratio(delta(run, key("engine_round_cpu_us", "sum")),
                 delta(run, key("engine_round_us", "sum", phase="total")),
                 100.0)

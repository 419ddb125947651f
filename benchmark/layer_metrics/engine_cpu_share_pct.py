"""engine step loop: of the CPU time the process got in the window
(``process_cpu_us``), the share its engine threads spent inside rounds
(``engine_round_cpu_us``, all engines together); the rest went to client
threads, tickers, the transport, the engine threads' idle passes and the
native threads of the runtime (on the chip's host most of it: the process runs
on two to three and a half cores, PERF.md section 6, PR 37)."""

from benchmark.window_registry import delta, key, ratio


def read(run):
    return ratio(delta(run, key("engine_round_cpu_us", "sum")),
                 delta(run, "process_cpu_us"), 100.0)

"""engine step loop: seconds of XLA backend compiles (or loads from the
persistent cache) of the process up to the window's end, every engine-round
phase and ``none`` (``xla_compile_us{phase}``, ``capacity.CompileListener``).
Cumulative, as ``start_replica_ms`` is: nothing compiles inside the window."""

from benchmark.window_registry import over_labels, ratio


def read(run):
    return ratio(over_labels(run.registry_after, "xla_compile_us", "sum"),
                 1.0, 1e-6)

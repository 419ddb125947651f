"""engine step loop: operations acknowledged in the window over the step
calls of all engines in it (operations per batch)."""


def read(run):
    calls = run.window_step_calls()
    if calls <= 0:
        return None
    return (run.acked_writes + run.acked_reads) / calls

"""engine step loop: mean thread CPU time of the engine thread in a round's
``fetch`` phase: the download (where the host waits for the device, off the CPU) and
the ``tolist`` of its ``active`` column.
``round_fetch_ms`` less this is what the thread spent blocked there.  (How the
mean is made of the rounds that read the CPU clock at their boundaries:
``round_stage_cpu_ms.py``.)"""

from benchmark.layer_metrics.round_stage_cpu_ms import phase_cpu_ms


def read(run):
    return phase_cpu_ms(run, "fetch")

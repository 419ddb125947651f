"""engine step loop: mean thread CPU time of the engine thread in a round's
``upload`` phase: the round's one ``device_put``, the jitted entry's call and the
rebinding of the resident state.
``round_upload_ms`` less this is what the thread spent blocked there.  (How the
mean is made of the rounds that read the CPU clock at their boundaries:
``round_stage_cpu_ms.py``.)"""

from benchmark.layer_metrics.round_stage_cpu_ms import phase_cpu_ms


def read(run):
    return phase_cpu_ms(run, "upload")

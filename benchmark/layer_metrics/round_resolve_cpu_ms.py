"""transport: mean thread CPU time of the engine thread in a round's
``resolve`` phase: proposal fates, the messages and updates built from the candidate
rows, both sends.
``round_resolve_ms`` less this is what the thread spent blocked there.  (How the
mean is made of the rounds that read the CPU clock at their boundaries:
``round_stage_cpu_ms.py``.)"""

from benchmark.layer_metrics.round_stage_cpu_ms import phase_cpu_ms


def read(run):
    return phase_cpu_ms(run, "resolve")

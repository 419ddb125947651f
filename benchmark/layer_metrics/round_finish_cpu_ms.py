"""apply: mean thread CPU time of the engine thread in a round's
``finish`` phase: commit caches, read completions, apply and the acknowledgements,
leader edges, the every-tenth-round collection.
``round_finish_ms`` less this is what the thread spent blocked there.  (How the
mean is made of the rounds that read the CPU clock at their boundaries:
``round_stage_cpu_ms.py``.)"""

from benchmark.layer_metrics.round_stage_cpu_ms import phase_cpu_ms


def read(run):
    return phase_cpu_ms(run, "finish")

"""apply: mean a round of the part of ``finish`` that acknowledges
(``engine_round_part_us.sum{part=finish.ack}``): on the replicas that hold
futures, ``book.committed`` and the ``book.applied`` loop, each answer waking
the client thread that waits on it.  (Host time: the thread's CPU clock costs
5.6 us a read on the chip's host, which a part entered once a row cannot pay;
``round_finish_cpu_ms`` is the CPU time of the whole phase.)"""

from benchmark.layer_metrics.finish_apply_ms import part_ms


def read(run):
    return part_ms(run, "finish.ack")

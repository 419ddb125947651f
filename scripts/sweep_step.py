"""Step-time sensitivity sweep on the live backend.

Measures steady-state step_ms for the self-driving bench loop across
kernel-geometry variations to locate the hot dimension (K inbox slots,
E entry lanes, CAP ring, B proposal width, G lanes).  Usage:

    python scripts/sweep_step.py [quick]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from dragonboat_tpu.hostenv import enable_compile_cache

enable_compile_cache()

from dragonboat_tpu.bench_loop import elect_all, make_cluster, run_steps
from dragonboat_tpu.core import params as KP


def measure(groups, cap=256, k=None, e=16, b=16, steps=20, replicas=3):
    import dataclasses

    from dragonboat_tpu.bench_loop import bench_params

    k = k if k is not None else 5 * (replicas - 1)
    # geometry overrides on top of bench_params so the sweep inherits
    # every platform-picked lowering flag (onehot_reads today, whatever
    # comes next) instead of hand-copying the pick
    kp = dataclasses.replace(
        bench_params(replicas),
        log_cap=cap, inbox_cap=k, msg_entries=e, proposal_cap=b,
        readindex_cap=4, apply_batch=2 * b, compaction_overhead=2 * b,
    )
    state = make_cluster(kp, groups, replicas)
    t0 = time.time()
    state, box = elect_all(kp, replicas, state)
    elect_s = time.time() - t0
    # warmup/compile the timed variant
    state, box = run_steps(kp, replicas, steps, True, True, state, box)
    state.term.block_until_ready()
    t0 = time.time()
    state, box = run_steps(kp, replicas, steps, True, True, state, box)
    state.committed.block_until_ready()
    dt = time.time() - t0
    lead = np.asarray(state.role) == KP.LEADER
    step_ms = dt / steps * 1e3
    wps = groups * b / (dt / steps)
    print(f"G={groups:<6} CAP={cap:<5} K={k:<3} E={e:<3} B={b:<3} "
          f"step_ms={step_ms:8.2f}  writes/s={wps:>12,.0f}  "
          f"(elect {elect_s:.1f}s, leaders {int(lead.sum())})", flush=True)
    return step_ms


if __name__ == "__main__":
    quick = len(sys.argv) > 1 and sys.argv[1] == "quick"
    print(f"backend: {jax.devices()[0].platform}", flush=True)
    base = dict(groups=1024, cap=256, k=10, e=16, b=16)
    measure(**base)
    if not quick:
        measure(**{**base, "groups": 256})
        measure(**{**base, "groups": 4096})
        measure(**{**base, "k": 4})
        measure(**{**base, "k": 2})
        measure(**{**base, "e": 4})
        measure(**{**base, "e": 1, "b": 1})
        measure(**{**base, "cap": 64})
        measure(**{**base, "cap": 1024})
        measure(**{**base, "b": 4})

"""Hotspot chaos fault end-to-end (the ISSUE 18 observe→act closure).

``run_hotspot(seed)`` drives a 3-replica device-resident cluster with
two shards, makes one shard's state machine pathologically slow to
apply under a 100:1 skewed write load, and requires the elastic control
plane to close the loop on its own: the step-latency EWMA trips the
host-hot gate, the fleet controller plans a leadership transfer for the
hot shard, the NodeHost issues it, and leadership actually leaves the
initial leader — all while the convergence oracle holds (zero acked
loss, equal journals, leaderless gauge drained, invariant probes
clean).

The scenario regression-covers two load-dependent liveness bugs this
closure flushed out: the kernel's campaign gate must not refuse
elections merely because apply backpressure keeps committed > applied
(core/kernel.py _campaign), and an armed-then-aborted leader transfer
must re-arm from the sticky lease instead of being lost
(engine/kernel_engine.py _stage_lane).

Budget: ~22 s per seed; two fixed seeds ride tier-1 as ``chaos_fast``.
"""

import pytest

from dragonboat_tpu.chaos import run_hotspot

FAST_SEEDS = (11, 23)


@pytest.mark.chaos_fast
@pytest.mark.parametrize("seed", FAST_SEEDS)
def test_hotspot_drains_and_converges(seed, one_core):
    r = run_hotspot(seed)
    assert r.report.ok, (seed, r.report.failures)
    assert r.transfers, (seed, "controller never planned a transfer")
    assert r.final_leader != r.initial_leader, (seed, r.final_leader)
    assert r.acked_count > 0, seed
    # every transfer decision carries its evidence row (the flight
    # record IS the audit trail the doctor replays)
    for t in r.transfers:
        ev = t.get("evidence", {})
        assert {"obs", "lane", "score", "lag", "streak",
                "term"} <= set(ev), (seed, t)

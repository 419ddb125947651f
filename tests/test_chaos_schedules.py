"""Composed chaos schedules end-to-end (the ISSUE 3 tentpole).

Every test runs ``run_schedule(seed)``: a fixed seed generates a
FaultPlan composing storage / transport / process faults, the runner
executes it against a 3-replica MemFS cluster under a write workload,
and the convergence oracle must hold — zero committed-entry loss,
identical committed prefixes, monotone applied indices, equal hash
oracles.

Two tiers:

- ``chaos_fast``: five seeds chosen to cover all three seams plus the
  deterministic-replay contract; wired into run_tests.sh tier-1 and the
  plain ``-m 'not slow'`` suite.  Budget: well under 60 s total.
- ``slow``: twenty more seeds for the nightly-style sweep
  (``pytest tests/test_chaos_schedules.py -m slow``).

Seed coverage (from FaultPlan.generate; see test_chaos_faults.py for
the generator invariants): seed 1 = kill + torn crash_write + breaker +
drop; 7 = partition + kill + delay; 9 = torn crash_write + duplicate;
13 = partition + clean crash_write + reorder; 25 = two crash_writes in
one schedule.
"""

import pytest

from dragonboat_tpu.chaos import FaultPlan, run_schedule

FAST_SEEDS = (1, 7, 9, 13, 25)
SLOW_SEEDS = (2, 3, 4, 5, 6, 8, 10, 11, 12, 14,
              15, 16, 17, 21, 22, 32, 36, 42, 47, 48)
assert len(FAST_SEEDS) + len(SLOW_SEEDS) >= 25
assert not set(FAST_SEEDS) & set(SLOW_SEEDS)


def _run_and_check(seed):
    r = run_schedule(seed)
    assert r.report.ok, (seed, r.report.failures)
    assert r.acked_count > 0, seed
    return r


@pytest.mark.chaos_fast
@pytest.mark.parametrize("seed", FAST_SEEDS)
def test_schedule_converges_fast(seed):
    _run_and_check(seed)


@pytest.mark.slow
@pytest.mark.parametrize("seed", SLOW_SEEDS)
def test_schedule_converges_slow(seed):
    _run_and_check(seed)


@pytest.mark.chaos_fast
def test_schedule_converges_on_pipelined_kernel():
    """Faults against device-resident shards served through the depth-1
    pipelined engine loop (PR 6): a kill/crash now lands while a donated
    step is in flight, and restart/recovery must still converge with the
    same oracle.  Seed 1 covers kill + torn crash_write + breaker + drop."""
    r = run_schedule(1, device_resident=True, pipeline_depth=1)
    assert r.report.ok, r.report.failures
    assert r.acked_count > 0


@pytest.mark.chaos_fast
def test_schedule_partitioned_mesh_link_falls_back_to_hub():
    """Round 17: the same composed schedule against MESH-resident shards
    (one shared ('g','r') engine, one replica per device).  Partition
    faults drive the full-row per-link cut mask, delay faults cut this
    host's mesh links onto the host hub (chaos/runner.py wires both
    through MeshDispatch.set_cut / set_link_hub_served), so consensus
    traffic for a cut link falls back to the hub — where the transport
    fault actually applies — or stalls safely.  The oracle still
    requires zero acked-entry loss and post-heal convergence.  Seed 7
    composes partition + kill + delay."""
    r = run_schedule(7, mesh_resident=True)
    assert r.report.ok, r.report.failures
    assert r.acked_count > 0


@pytest.mark.chaos_fast
def test_probe_catches_commit_without_quorum_mutation(monkeypatch):
    """Mutation acceptance for the runtime invariant probe (ISSUE 14):
    a kernel seeded with the commit-without-quorum bug from the model
    checker's catalogue, serving a LIVE 3-replica device-resident
    cluster, must trip ``leader_commit_quorum`` — the flight recorder
    carries the invariant_violation edge, ``violations_seen`` latches,
    and /healthz degrades to 503 (stickily: a violation is a bug, not a
    condition that clears)."""
    import importlib.util
    import json
    import os
    import sys
    import time

    from dragonboat_tpu import flight
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.engine import kernel_engine as ke
    from dragonboat_tpu.server.metrics_http import MetricsServer

    from test_kernel_engine import close_all, make_cluster, propose_retry
    from test_nodehost import wait_leader

    mc_path = os.path.join(os.path.dirname(__file__), os.pardir,
                           "scripts", "model_check.py")
    spec = importlib.util.spec_from_file_location("_chaos_model_check",
                                                  mc_path)
    mc = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mc
    spec.loader.exec_module(mc)
    mut = mc.load_kernel_module("commit_without_quorum")
    # the engine binds this module global at construction time (both of
    # its round entries wrap it, core/round.py)
    monkeypatch.setattr(ke, "kernel_step", mut.step)

    hosts = make_cluster("mutq", expert=ExpertConfig(
        kernel_log_cap=256, kernel_capacity=8, kernel_apply_batch=16,
        kernel_compaction_overhead=16, fleet_stats_every=1))
    server = None
    try:
        lead = wait_leader(hosts, timeout=30)
        nh = hosts[lead]
        sess = nh.get_noop_session(1)
        # keep proposing so the mutated leader path (commit = last,
        # quorum unconsulted) keeps advancing ahead of the acks; the
        # probe rides every step at fleet_stats_every=1
        deadline = time.time() + 30
        snap = nh._invariants_snapshot()
        i = 0
        while time.time() < deadline and not snap["violations_seen"]:
            try:
                propose_retry(nh, sess, f"m{i}=x".encode(), deadline_s=2)
            except Exception:
                pass
            i += 1
            snap = nh._invariants_snapshot()
        assert snap["violations_seen"] > 0, snap
        assert snap["per_invariant"]["leader_commit_quorum"] > 0 \
            or snap["first"] is not None, snap
        assert any(r.get("kind") == flight.INVARIANT_VIOLATION
                   for r in flight.RECORDER.tail(256)), \
            "no invariant_violation flight record"
        server = MetricsServer(
            [nh.events.metrics.registry],
            invariants_source=nh._invariants_snapshot)
        status, body, _ = server.healthz()
        assert status == 503, (status, body)
        assert json.loads(body)["invariants"]["violations_seen"] > 0
    finally:
        if server is not None:
            server.close()
        close_all(hosts)


@pytest.mark.chaos_fast
def test_schedule_trace_is_byte_identical_and_replayable():
    """The deterministic-replay contract (COVERAGE.md): the same seed
    twice yields byte-identical fault traces, and the recorded plan JSON
    replays to the same trace."""
    a = _run_and_check(9)
    b = _run_and_check(9)
    assert a.trace_json == b.trace_json
    assert a.plan_json == b.plan_json
    replay = run_schedule(9, plan=FaultPlan.from_json(a.plan_json))
    assert replay.report.ok, replay.report.failures
    assert replay.trace_json == a.trace_json

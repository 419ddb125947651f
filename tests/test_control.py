"""Elastic fleet control plane (dragonboat_tpu/control.py): the pure
planner's determinism, hysteresis, rate limiting and cooldown; the
capacity admission gate's modes; and the NodeHost admission wiring
(structured refusal + counters + flight record)."""

from __future__ import annotations

import pytest

from dragonboat_tpu import control


def _row(lane, score=10, lag=0, classes=("commit_stall",)):
    return {"lane": lane, "score": score, "lag": lag,
            "classes": list(classes)}


def _shard(sid, lane, leader=True, term=3, voters=(1, 2, 3), rid=1):
    return {
        "shard_id": sid, "replica_id": rid, "lane": lane,
        "is_leader": leader, "term": term,
        "membership": {"addresses": {v: "" for v in voters}},
    }


def _ctl(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("hysteresis", 1)
    kw.setdefault("warmup_obs", 0)
    return control.FleetController(control.ControlPolicy(**kw))


# -- target selection ---------------------------------------------------


def test_pick_target_deterministic_and_excludes_self():
    a = control.pick_target(7, 42, 3, (1, 2, 3), exclude=1)
    b = control.pick_target(7, 42, 3, (1, 2, 3), exclude=1)
    assert a == b and a in (2, 3)


def test_pick_target_varies_with_term_and_seed():
    picks = {control.pick_target(7, 42, t, tuple(range(1, 9)), 1)
             for t in range(16)}
    assert len(picks) > 1        # term in the key: a retry can move
    picks = {control.pick_target(s, 42, 3, tuple(range(1, 9)), 1)
             for s in range(16)}
    assert len(picks) > 1


def test_pick_target_singleton_returns_zero():
    assert control.pick_target(0, 1, 1, (5,), exclude=5) == 0


# -- planner ------------------------------------------------------------


def test_transfer_planned_for_hot_leader():
    c = _ctl()
    ds = c.observe([_row(0, score=10)], [_shard(100, 0)])
    assert len(ds) == 1
    d = ds[0]
    assert d.kind == control.TRANSFER
    assert d.shard_id == 100 and d.target in (2, 3)
    assert d.evidence["score"] == 10 and d.evidence["lane"] == 0
    assert d.evidence["classes"] == ["commit_stall"]


def test_identical_observations_plan_identically():
    worst, shards = [_row(0), _row(1)], [_shard(100, 0), _shard(101, 1)]
    plan = lambda: _ctl(max_transfers=8).observe(worst, shards)
    assert plan() == plan()


def test_not_leader_never_transfers():
    c = _ctl()
    assert c.observe([_row(0)], [_shard(100, 0, leader=False)]) == []


def test_cold_shard_not_transferred():
    c = _ctl(hot_score=8, lag_hot=64)
    assert c.observe([_row(0, score=3, lag=5)], [_shard(100, 0)]) == []


def test_lag_alone_trips_hot():
    c = _ctl(hot_score=8, lag_hot=64)
    ds = c.observe([_row(0, score=1, lag=100)], [_shard(100, 0)])
    assert len(ds) == 1


def test_disabled_policy_plans_nothing():
    c = _ctl(enabled=False)
    assert c.observe([_row(0)], [_shard(100, 0)]) == []


def test_hysteresis_requires_consecutive_hot():
    c = _ctl(hysteresis=3)
    assert c.observe([_row(0)], [_shard(100, 0)]) == []
    assert c.observe([_row(0)], [_shard(100, 0)]) == []
    assert len(c.observe([_row(0)], [_shard(100, 0)])) == 1


def test_hysteresis_streak_resets_when_cold():
    c = _ctl(hysteresis=2)
    assert c.observe([_row(0)], [_shard(100, 0)]) == []
    # shard drops out of the digest entirely: streak must restart
    assert c.observe([], []) == []
    assert c.observe([_row(0)], [_shard(100, 0)]) == []
    assert len(c.observe([_row(0)], [_shard(100, 0)])) == 1


def test_max_transfers_per_observation():
    c = _ctl(max_transfers=2)
    worst = [_row(i, score=20 - i) for i in range(5)]
    shards = [_shard(100 + i, i) for i in range(5)]
    ds = c.observe(worst, shards)
    assert len(ds) == 2
    # severity order: the two hottest lanes moved first
    assert [d.shard_id for d in ds] == [100, 101]


def test_cooldown_blocks_repeat_transfer():
    c = _ctl(cooldown_obs=3)
    assert len(c.observe([_row(0)], [_shard(100, 0)])) == 1
    assert c.observe([_row(0)], [_shard(100, 0)]) == []   # obs 2
    assert c.observe([_row(0)], [_shard(100, 0)]) == []   # obs 3
    assert len(c.observe([_row(0)], [_shard(100, 0)])) == 1  # obs 4


def test_host_hot_drains_every_led_shard():
    c = _ctl(hot_score=1000, lag_hot=10**6)
    # nothing trips per-lane thresholds, but the host itself is hot:
    # every led shard is a candidate, digest row or not (host-level
    # overload is not attributable to one lane), in severity order
    ds = c.observe([_row(0, score=1)],
                   [_shard(100, 0), _shard(101, 7)], host_hot=True)
    assert [d.shard_id for d in ds] == [100, 101]
    assert ds[0].evidence["host_hot"] is True
    assert ds[1].evidence["score"] == 0       # lane 7: no digest row


def test_warmup_suppresses_host_hot_not_digest():
    c = _ctl(warmup_obs=2, hot_score=8)
    # obs 1-2: host_hot alone is compile noise, ignored...
    assert c.observe([], [_shard(100, 0)], host_hot=True) == []
    # ...but a genuine digest verdict still acts during warmup
    assert len(c.observe([_row(1, score=10)],
                         [_shard(200, 1)], host_hot=False)) == 1
    # obs 3: past the warmup, host_hot drains again
    assert len(c.observe([], [_shard(100, 0)], host_hot=True)) == 1


def test_singleton_skipped_but_next_candidate_taken():
    c = _ctl(max_transfers=1)
    worst = [_row(0, score=20), _row(1, score=10)]
    shards = [_shard(100, 0, voters=(1,)), _shard(101, 1)]
    ds = c.observe(worst, shards)
    assert [d.shard_id for d in ds] == [101]


# -- admission ----------------------------------------------------------


def test_admission_limit_derates_by_watermark():
    fake = lambda kp, budget: 100
    assert control.admission_limit(None, 1 << 30, 10.0, fake) == 90
    assert control.admission_limit(None, 0, 10.0, fake) == 0
    assert control.admission_limit(None, 1 << 30, 100.0, fake) == 1


def test_check_admission_modes():
    assert control.check_admission(1, 5, 10) is None
    d = control.check_admission(1, 10, 10)
    assert d is not None and d.kind == control.REFUSE
    assert d.evidence == {"occupied": 10, "limit": 10, "mode": "enforce"}
    assert control.check_admission(1, 10, 10,
                                   mode=control.ADMISSION_OFF) is None
    w = control.check_admission(1, 10, 10, mode=control.ADMISSION_WARN)
    assert w is not None and w.evidence["mode"] == "warn"
    # no resolvable budget: never refuse
    assert control.check_admission(1, 10, 0) is None


# -- NodeHost wiring ----------------------------------------------------


@pytest.fixture
def host(tmp_path):
    from dragonboat_tpu.config import NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost

    nhc = NodeHostConfig(raft_address="adm-1:9001", deployment_id=1)
    nhc.expert.admission_policy = control.ADMISSION_ENFORCE
    # a budget that models exactly 2 lanes, zero watermark so limit == 2
    from dragonboat_tpu import capacity as _capacity

    nhc.expert.kernel_log_cap = 64
    nhc.expert.kernel_inbox_cap = 4
    nhc.expert.kernel_msg_entries = 4
    nhc.expert.kernel_proposal_cap = 2
    nhc.expert.capacity_watermark_pct = 0.0
    nh = NodeHost(nhc, auto_run=False)
    per = _capacity.model_bytes_per_group(
        nh._kernel_params(), _capacity.RESIDENT_CLASSES,
        _capacity.PACKED_RESIDENT)["total"]
    nhc.expert.capacity_device_budget_bytes = 2 * per
    yield nh
    nh.close()


def _start(nh, sid, device=True):
    from dragonboat_tpu.config import Config
    from test_nodehost import KVStateMachine

    nh.start_replica(
        {1: nh.raft_address}, False, KVStateMachine,
        Config(shard_id=sid, replica_id=1, election_rtt=10,
               heartbeat_rtt=1, snapshot_entries=0,
               device_resident=device))


def test_nodehost_admission_refuses_past_watermark(host):
    from dragonboat_tpu import flight
    from dragonboat_tpu.nodehost import AdmissionRefusedError

    _start(host, 1)
    _start(host, 2)
    with pytest.raises(AdmissionRefusedError) as ei:
        _start(host, 3)
    assert ei.value.evidence["occupied"] == 2
    assert ei.value.evidence["limit"] == 2
    m = host.metrics()
    assert m.get("control_admission_total") == 3
    assert m.get("control_admission_refused") == 1
    kinds = [r["kind"] for r in flight.RECORDER.tail()]
    assert flight.ADMISSION_REFUSED in kinds
    # host-resident replicas bypass the device admission gate
    _start(host, 4, device=False)
    assert host.metrics().get("control_admission_total") == 3


def test_nodehost_admission_warn_admits(host):
    host.config.expert.admission_policy = control.ADMISSION_WARN
    for sid in (1, 2, 3):
        _start(host, sid)
    m = host.metrics()
    assert m.get("control_admission_refused") == 1
    assert 3 in host.nodes


# -- fleet_doctor --plan (read-only dry run) ----------------------------


def _plan_info(worst=(), shards=(), capacity=None, quiesced=0):
    """A minimal valid NodeHost.info() payload for the doctor."""
    from dragonboat_tpu.core import health

    h = health.empty_dict()
    h["worst"] = list(worst)
    h["anomalous"] = len(h["worst"])
    for w in h["worst"]:
        for c in w["classes"]:
            h["class_count"][c] += 1
    info = {"node_host_id": "nhid-plan", "raft_address": "p-1",
            "health": h, "shards": list(shards)}
    if capacity is not None:
        info["capacity"] = capacity
    info["fleet"] = {"quiesced": quiesced}
    return info


def _offender(lane, score=24, classes=("leaderless",)):
    from dragonboat_tpu.core import health

    return dict({f: 0 for f in health.ROW_FIELDS}, lane=lane, score=score,
                flags=1, classes=list(classes), engine="kernel")


def _info_shard(sid, lane, leader=True, resident="device"):
    return {"shard_id": sid, "replica_id": 1, "leader_id": 1, "term": 5,
            "is_leader": leader, "last_applied": 0,
            "membership": {"addresses": {1: "p-1", 2: "p-2", 3: "p-3"},
                           "non_votings": {}, "witnesses": {},
                           "config_change_id": 1},
            "resident": resident, "lane": lane}


def _doctor():
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "fleet_doctor", os.path.join(root, "scripts", "fleet_doctor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_plan_schema_is_strict():
    ds = [control.Decision(
        kind=control.TRANSFER, shard_id=7, target=2,
        evidence={"obs": 1, "lane": 3, "score": 24, "lag": 0, "streak": 1,
                  "term": 5, "host_hot": False, "classes": ["leaderless"]}),
          control.Decision(
        kind=control.REFUSE, shard_id=0, target=0,
        evidence={"occupied": 4, "limit": 4, "mode": "warn"})]
    plan = control.plan_to_dict(ds, quiesced=2)
    control.validate_plan(plan)
    assert plan["counts"] == {"transfer": 1, "refuse": 1, "quiesced": 2}

    with pytest.raises(ValueError, match="keys"):
        control.validate_plan(dict(plan, extra=1))
    bad = control.plan_to_dict(ds, quiesced=2)
    bad["counts"]["transfer"] = 5
    with pytest.raises(ValueError, match="counts"):
        control.validate_plan(bad)
    bad = control.plan_to_dict(ds, quiesced=2)
    del bad["transfers"][0]["evidence"]["score"]
    with pytest.raises(ValueError, match="score"):
        control.validate_plan(bad)
    bad = control.plan_to_dict(ds, quiesced=2)
    bad["refusals"][0]["evidence"]["mode"] = "bogus"
    with pytest.raises(ValueError, match="mode"):
        control.validate_plan(bad)
    bad = control.plan_to_dict(ds, quiesced=2)
    bad["counts"]["quiesced"] = True
    with pytest.raises(ValueError, match="quiesced"):
        control.validate_plan(bad)


def test_build_plan_dry_run():
    fd = _doctor()
    # hot led shard on lane 3, host at its modeled device capacity,
    # two lanes masked-quiesced: all three verbs show up
    info = _plan_info(
        worst=[_offender(3)],
        shards=[_info_shard(7, 3), _info_shard(8, 4),
                _info_shard(9, -1, resident="host")],
        capacity={"model_max_g_at_budget": 2}, quiesced=2)
    plan = fd.build_plan(info)
    control.validate_plan(plan)
    assert plan["counts"] == {"transfer": 1, "refuse": 1, "quiesced": 2}
    t = plan["transfers"][0]
    assert t["shard_id"] == 7 and t["target"] in (2, 3)
    assert t["evidence"]["score"] == 24
    # host-resident shard 9 is not admission-relevant: occupied == 2
    assert plan["refusals"][0]["evidence"] == {
        "occupied": 2, "limit": 2, "mode": "warn"}
    # healthy host, capacity headroom: empty plan
    empty = fd.build_plan(_plan_info(
        shards=[_info_shard(7, 3)],
        capacity={"model_max_g_at_budget": 8}))
    control.validate_plan(empty)
    assert empty["counts"] == {"transfer": 0, "refuse": 0, "quiesced": 0}


def test_fleet_doctor_plan_cli(capsys):
    import json
    import sys

    from dragonboat_tpu.server.metrics_http import MetricsServer

    fd = _doctor()
    state = {"i": _plan_info(worst=[_offender(3)],
                             shards=[_info_shard(7, 3)], quiesced=1)}
    srv = MetricsServer([], address="127.0.0.1:0",
                        health_source=lambda: state["i"]["health"],
                        info_source=lambda: state["i"],
                        shard_info_source=lambda sid: None)
    argv = sys.argv
    try:
        # pending transfer -> exit 1, human report carries evidence
        sys.argv = ["fleet_doctor.py", srv.address, "--plan"]
        assert fd.main() == 1
        out = capsys.readouterr().out
        assert "transfers=1" in out and "quiesced=1" in out
        assert "transfer shard 7" in out and "score=24" in out
        # --json round-trips through the strict schema
        sys.argv = ["fleet_doctor.py", srv.address, "--plan", "--json"]
        assert fd.main() == 1
        plan = json.loads(capsys.readouterr().out)["plan"]
        control.validate_plan(plan)
        assert plan["counts"]["transfer"] == 1
        # nothing hot -> empty plan, exit 0
        state["i"] = _plan_info(shards=[_info_shard(7, 3)])
        sys.argv = ["fleet_doctor.py", srv.address, "--plan"]
        assert fd.main() == 0
        assert "nothing pending" in capsys.readouterr().out
    finally:
        sys.argv = argv
        srv.close()

"""Served width: three NodeHosts whose engines are 4,096 lanes tall
(``ExpertConfig.kernel_capacity`` as ``fleet-4k`` states it) and whose groups
sit on the LAST lanes of them, through the entry points a user calls.  The
device programs alone are held row for row in ``tests/test_wide_rows.py``.
"""

from __future__ import annotations

import time

import numpy as np

from dragonboat_tpu import telemetry, tracing
from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
from dragonboat_tpu.engine.kernel_engine import KernelEngine
from dragonboat_tpu.nodehost import NodeHost

from test_fleet_cell import wait_for
from test_kernel_engine import propose_retry
from test_nodehost import KVStateMachine, wait_leader

WIDE = 4096

def test_groups_on_the_last_lanes_of_4096_serve_quiesce_and_wake(monkeypatch):
    """Three NodeHosts with ``kernel_capacity`` 4096 whose engines hand out
    their lanes from the top (4,095 down): three groups take acknowledged
    writes and a linearizable read from the leader's host and a follower's,
    held against a dict fed the acknowledged writes; the third group, left
    alone with ``quiesce`` on, falls asleep on all three engines and a write
    wakes it."""
    built = KernelEngine.__init__

    def top_down(self, *a, **kw):
        built(self, *a, **kw)
        self._free.reverse()            # pop() hands out 4095, 4094, ...

    monkeypatch.setattr(KernelEngine, "__init__", top_down)
    prefix = f"wide-{time.monotonic_ns()}"
    addrs = {i: f"{prefix}-{i}" for i in (1, 2, 3)}
    shards, sleeper = (1, 2, 3), 3
    hosts = {}
    try:
        for rid, addr in addrs.items():
            hosts[rid] = nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=5,
                expert=ExpertConfig(kernel_capacity=WIDE, kernel_log_cap=64,
                                    kernel_apply_batch=16,
                                    kernel_compaction_overhead=8)))
            for sid in shards:
                nh.start_replica(addrs, False, KVStateMachine, Config(
                    shard_id=sid, replica_id=rid, election_rtt=5,
                    heartbeat_rtt=1, device_resident=True, quiesce=True))
        engines = [nh.kernel_engine for nh in hosts.values()]
        assert all(e.capacity == WIDE for e in engines)
        lanes = sorted(nh.nodes[sid].lane for nh in hosts.values()
                       for sid in shards)
        assert lanes == sorted([4095, 4094, 4093] * 3)
        snap = telemetry.GLOBAL.snapshot()
        assert all(snap[f"engine_lanes{{what=capacity,engine={e.label}}}"]
                   == WIDE for e in engines)

        def asleep(sid):
            out = []
            for nh in hosts.values():
                eng, node = nh.kernel_engine, nh.nodes[sid]
                with eng.mu:
                    out.append(bool(np.asarray(eng.state.quiesced)[node.lane]))
            return out

        def write_and_read_back(sid, items: dict, plain: dict):
            lid = wait_leader(hosts, shard_id=sid, timeout=90)
            sess = hosts[lid].get_noop_session(sid)
            for k, v in items.items():
                propose_retry(hosts[lid], sess, f"{k}={v}".encode(),
                              deadline_s=60)
                plain[k] = v                    # acknowledged: it counts
            follower = next(r for r in hosts if r != lid)
            for k, want in plain.items():
                for rid in (lid, follower):
                    assert hosts[rid].sync_read(
                        sid, k, timeout_s=30) == want, (sid, rid, k)

        plain = {sid: {} for sid in shards}
        for sid in (1, 2):
            write_and_read_back(
                sid, {f"k{sid}-{i}": f"v{i}" for i in range(12)}, plain[sid])
            write_and_read_back(sid, {f"k{sid}-3": "again"}, plain[sid])
        wait_leader(hosts, shard_id=sleeper, timeout=90)
        deadline = time.monotonic() + 120
        while not all(asleep(sleeper)):
            assert time.monotonic() < deadline, (
                f"shard {sleeper} still awake: {asleep(sleeper)}")
            time.sleep(0.25)
        held = [rec["lanes_held"] for rec in tracing.ROUNDS.rounds()
                if rec.get("engine") in {nh.id for nh in hosts.values()}]
        assert held and held[-1] == len(shards)
        write_and_read_back(sleeper, {"woken": "yes"}, plain[sleeper])
        assert not any(asleep(sleeper))
        for sid in shards:                      # every replica, the dict
            for nh in hosts.values():
                assert wait_for(
                    lambda: nh.nodes[sid].sm.sm.kv == plain[sid], 30)
    finally:
        for nh in hosts.values():
            nh.close()
        tracing.ROUNDS.reset()


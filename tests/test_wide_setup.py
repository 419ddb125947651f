"""What 12,288 ``start_replica`` calls and 4,096 elections at once
(``fleet-4k``'s set-up) asked of the program, held small on the CPU: a host
handed replicas in a row does not relist all it holds after each, and the
peer-book writes of the rows one round retires go to the device as one
batch."""

from __future__ import annotations

import time

import numpy as np

from dragonboat_tpu import capacity
from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
from dragonboat_tpu.core import params as KP
from dragonboat_tpu.nodehost import NodeHost

from test_kernel_engine import propose_retry
from test_nodehost import KVStateMachine


def host(prefix, auto_run, capacity_lanes=64):
    return NodeHost(NodeHostConfig(
        raft_address=f"{prefix}-1", rtt_millisecond=5,
        expert=ExpertConfig(kernel_log_cap=64, kernel_apply_batch=16,
                            kernel_compaction_overhead=8,
                            kernel_capacity=capacity_lanes)),
        auto_run=auto_run)


def start(nh, prefix, sid, device=True):
    nh.start_replica({1: f"{prefix}-1"}, False, KVStateMachine, Config(
        shard_id=sid, replica_id=1, election_rtt=10, heartbeat_rtt=2,
        device_resident=device))


def test_replicas_started_in_a_row_join_the_views_in_place():
    """The views stay current through every ``start_replica`` (no walker
    has to relist the host's nodes), hold what a relisting would, and a
    change of any other kind still relists."""
    prefix = f"wide-v-{time.monotonic_ns()}"
    nh = host(prefix, auto_run=False)
    try:
        assert nh._node_views() == ([[] for _ in range(nh._num_workers)], [])
        relisted = []

        class Counting(dict):
            def items(self):
                relisted.append(len(self))
                return dict.items(self)

        nh.nodes = Counting(nh.nodes)
        for sid in range(1, 25):
            start(nh, prefix, sid, device=sid % 4 != 0)
            assert nh._views[0] == nh._nodes_version
        shares, driven = nh._node_views()
        assert relisted == [], "a start_replica made a walker relist"
        assert sorted(n.shard_id for n in driven) == [
            s for s in range(1, 25) if s % 4]
        for w, share in enumerate(shares):
            assert sorted(n.shard_id for n in share) == [
                s for s in range(4, 25, 4) if s % nh._num_workers == w]
        # what a relisting gives is what the views hold
        nh._nodes_version += 1
        again = nh._node_views()
        assert relisted == [24]
        assert [sorted(n.shard_id for n in x) for x in (*again[0], again[1])
                ] == [sorted(n.shard_id for n in x)
                      for x in (*shares, driven)]
        nh.stop_replica(4)
        assert nh._views[0] != nh._nodes_version       # relists at next walk
        assert 4 not in {n.shard_id for share in nh._node_views()[0]
                         for n in share}
    finally:
        nh.close()


def test_the_peer_books_of_a_rounds_rows_go_up_as_one_batch():
    """Two dozen single-member groups on one engine elect themselves within a
    dozen ticks of each other and each applies its bootstrap config change:
    a ``membership_up`` crossing a ROUND that applied any, not one a
    replica; every lane's book and its config-change gate read what a
    write a replica left; the groups serve."""
    prefix = f"wide-m-{time.monotonic_ns()}"
    shards = range(1, 25)
    nh = host(prefix, auto_run=False)
    try:
        for sid in shards:
            start(nh, prefix, sid)
        eng = nh.kernel_engine
        before = capacity.METER.counts().get("membership_up", 0)
        rounds = 0
        deadline = time.monotonic() + 60
        while not all(nh.get_leader_id(sid) == (1, True) for sid in shards):
            assert time.monotonic() < deadline, "no leaders"
            nh.tick_all()
            rounds += bool(eng.step_all())
        for _ in range(6):                      # the no-op commits, applies
            nh.tick_all()
            rounds += bool(eng.step_all())
        applied = all(n.sm.get_membership().addresses == {1: f"{prefix}-1"}
                      for n in nh.nodes.values())
        assert applied
        crossings = capacity.METER.counts().get("membership_up", 0) - before
        assert 1 <= crossings <= 14 < len(shards), (crossings, rounds)
        assert eng._held_cells == []
        with eng.mu:
            state = eng.state
            lanes = [nh.nodes[sid].lane for sid in shards]
            pid = np.asarray(state.pid)[lanes]
            kind = np.asarray(state.kind)[lanes]
            assert (pid[:, 0] == 1).all() and not pid[:, 1:].any()
            assert (kind[:, 0] == KP.K_VOTER).all() and not kind[:, 1:].any()
            assert not np.asarray(state.pending_cc)[lanes].any()
        assert (eng._pid_np[lanes] == pid).all()
    finally:
        nh.close()
    # and under threads, through the entry points: the groups serve
    prefix += "-run"
    nh = host(prefix, auto_run=True)
    try:
        for sid in (1, 2, 3):
            start(nh, prefix, sid)
        for sid in (1, 2, 3):
            propose_retry(nh, nh.get_noop_session(sid), b"k=v", deadline_s=60)
            assert nh.sync_read(sid, "k", timeout_s=30) == "v"
    finally:
        nh.close()


def test_a_vacated_lanes_held_write_is_dropped():
    """A lane cleared while its peer-book write is held (an eviction inside
    the same ``_finish``) stays cleared: the batch leaves it out, and the
    last write of a cell is the one that lands."""
    prefix = f"wide-h-{time.monotonic_ns()}"
    nh = host(prefix, auto_run=False)
    try:
        for sid in (1, 2):
            start(nh, prefix, sid)
        eng = nh.kernel_engine
        nh.tick_all()
        eng.step_all()                          # both lanes injected
        keep, gone = nh.nodes[1], nh.nodes[2]
        before = capacity.METER.counts().get("membership_up", 0)
        eng.update_lane_membership(keep)
        eng.update_lane_membership(gone)
        eng._held_cells += ((keep.lane, "pending_cc", True),
                            (keep.lane, "pending_cc", False))
        assert capacity.METER.counts().get("membership_up", 0) == before
        assert len(eng._held_cells) == 8
        eng.remove_shard(2)                     # clears the lane at once
        eng._write_held_cells()
        assert capacity.METER.counts()["membership_up"] == before + 1
        assert eng._held_cells == []
        with eng.mu:
            state = eng.state
            assert not np.asarray(state.kind)[gone.lane].any()
            assert not np.asarray(state.pid)[gone.lane].any()
            assert np.asarray(state.kind)[keep.lane].tolist() == [
                KP.K_VOTER, 0, 0, 0, 0]
            assert not np.asarray(state.pending_cc)[keep.lane]
    finally:
        nh.close()

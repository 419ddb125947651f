"""Node-level operational features: quiesce, leader-transfer completion,
log query / compaction through the engine path, event listeners, metrics.

Reference behaviors: quiesce.go + quiesce_test.go, node.go:308
(processLeaderUpdate), node.go:1238/319 (log query), node.go:972
(requestCompaction), raftio/listener.go + event.go:54-90.
"""

import threading
import time

import pytest

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.config import Config, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.quiesce import QuiesceState
from dragonboat_tpu.request import RequestError, RequestRejectedError
from dragonboat_tpu.statemachine import IStateMachine, Result

from test_nodehost import KVStateMachine, wait_leader


def make_cluster(quiesce=False, snapshot_entries=0, rtt_ms=5, prefix="ops",
                 raft_listener=None, system_listener=None, election_rtt=10):
    addrs = {i: f"{prefix}-{i}" for i in range(1, 4)}
    hosts = {}
    for rid, addr in addrs.items():
        nh = NodeHost(NodeHostConfig(
            raft_address=addr, rtt_millisecond=rtt_ms,
                        raft_event_listener=raft_listener,
            system_event_listener=system_listener,
        ))
        cfg = Config(shard_id=1, replica_id=rid, election_rtt=election_rtt,
                     heartbeat_rtt=1, snapshot_entries=snapshot_entries,
                     compaction_overhead=5, quiesce=quiesce)
        nh.start_replica(addrs, False, KVStateMachine, cfg)
        hosts[rid] = nh
    return hosts


def close_all(hosts):
    for nh in hosts.values():
        nh.close()


# ---------------------------------------------------------------------------
# QuiesceState unit behavior (quiesce_test.go analogs)
# ---------------------------------------------------------------------------


class TestQuiesceState:
    def mk(self):
        return QuiesceState(shard_id=1, replica_id=1, election_tick=10,
                            enabled=True)

    def test_enters_quiesce_after_idle_threshold(self):
        q = self.mk()
        for _ in range(q.threshold() + 1):
            assert not q.quiesced()
            q.tick()
        assert q.quiesced()
        assert q.new_quiesce_state()
        assert not q.new_quiesce_state()  # one-shot flag

    def test_activity_resets_idle_clock(self):
        q = self.mk()
        for _ in range(q.threshold() - 1):
            q.tick()
        q.record(pb.MessageType.PROPOSE)
        for _ in range(q.threshold() - 1):
            q.tick()
        assert not q.quiesced()

    def test_message_exits_quiesce(self):
        q = self.mk()
        for _ in range(q.threshold() + 1):
            q.tick()
        assert q.quiesced()
        q.record(pb.MessageType.PROPOSE)
        assert not q.quiesced()

    def test_trailing_heartbeat_does_not_wake_fresh_quiesce(self):
        q = self.mk()
        for _ in range(q.threshold() + 1):
            q.tick()
        assert q.quiesced()
        q.record(pb.MessageType.HEARTBEAT)  # inside grace window
        assert q.quiesced()
        for _ in range(q.election_tick + 1):
            q.tick()
        q.record(pb.MessageType.HEARTBEAT)  # past grace window
        assert not q.quiesced()

    def test_try_enter_quiesce_respects_recent_exit(self):
        q = self.mk()
        for _ in range(q.threshold() + 1):
            q.tick()
        q.record(pb.MessageType.PROPOSE)  # exit
        q.try_enter_quiesce()             # just exited → refuse
        assert not q.quiesced()
        for _ in range(q.threshold() + 1):
            q.tick()
        q.try_enter_quiesce()
        assert q.quiesced()

    def test_disabled_is_inert(self):
        q = QuiesceState(election_tick=10, enabled=False)
        for _ in range(1000):
            q.tick()
        assert not q.quiesced()


# ---------------------------------------------------------------------------
# End-to-end quiesce: idle cluster goes quiet, proposal wakes it
# ---------------------------------------------------------------------------


def test_cluster_quiesces_and_wakes():
    hosts = make_cluster(quiesce=True, rtt_ms=2, prefix="qui",
                         election_rtt=5)
    try:
        lead = wait_leader(hosts)
        nh = hosts[lead]
        sess = nh.get_noop_session(1)
        nh.sync_propose(sess, b"k0=v0")
        # idle long enough for every node to pass threshold (50 ticks @2ms)
        deadline = time.time() + 10
        while time.time() < deadline:
            if all(n.nodes[1].qs.quiesced() for n in hosts.values()):
                break
            time.sleep(0.05)
        assert all(n.nodes[1].qs.quiesced() for n in hosts.values()), \
            "cluster did not quiesce"
        # a quiesced shard must not hold elections: terms stay put
        terms = {r: n.nodes[1].peer.raft.term for r, n in hosts.items()}
        time.sleep(0.3)
        assert terms == {r: n.nodes[1].peer.raft.term
                         for r, n in hosts.items()}
        # a proposal wakes the group and still commits
        lead = wait_leader(hosts)
        nh = hosts[lead]
        nh.sync_propose(nh.get_noop_session(1), b"k1=v1")
        assert nh.stale_read(1, "k1") == "v1"
        assert not hosts[lead].nodes[1].qs.quiesced()
    finally:
        close_all(hosts)


# ---------------------------------------------------------------------------
# leader transfer future completion
# ---------------------------------------------------------------------------


def test_leader_transfer_future_completes():
    hosts = make_cluster(prefix="xfer")
    try:
        # raft abandons a transfer that does not land inside one election
        # timeout (10 ticks of 5 ms here), and the future then times out:
        # "the timeout is the failure signal" (node._on_leader_update), and
        # the caller asks again, on whoever leads by then.  Under a loaded
        # box the first attempt can miss its 50 ms; one attempt must
        # complete with the target it named.
        for _ in range(5):
            lead = wait_leader(hosts)
            target = next(r for r in hosts if r != lead)
            rs = hosts[lead].nodes[1].request_leader_transfer(target, 400)
            hosts[lead]._work.set()
            # the book holds one request at a time and frees the slot at
            # the 400-tick deadline: outwait it rather than race it
            r = rs.wait(30.0)
            if r.code.name == "COMPLETED":
                break
            time.sleep(0.1)     # let raft drop the abandoned transfer
        assert r.code.name == "COMPLETED", r.code
        assert r.result.value == target
        assert wait_leader(hosts) == target
    finally:
        close_all(hosts)


# ---------------------------------------------------------------------------
# log query + compaction through the engine path
# ---------------------------------------------------------------------------


def test_query_raft_log_engine_path():
    hosts = make_cluster(prefix="lq")
    try:
        lead = wait_leader(hosts)
        nh = hosts[lead]
        sess = nh.get_noop_session(1)
        for i in range(5):
            nh.sync_propose(sess, f"k{i}=v{i}".encode())
        applied = nh.nodes[1].sm.get_last_applied()
        res = nh.query_raft_log(1, 1, applied + 1)
        assert res.error == 0
        assert res.entries, "no entries returned"
        assert res.entries[-1].index <= applied
        # out-of-range query → rejected
        with pytest.raises(RequestError):
            nh.query_raft_log(1, applied + 100, applied + 200, timeout_s=2.0)
    finally:
        close_all(hosts)


def test_sync_request_compaction():
    hosts = make_cluster(prefix="cpt")
    try:
        lead = wait_leader(hosts)
        nh = hosts[lead]
        # before any snapshot: nothing to compact
        with pytest.raises(RequestRejectedError):
            nh.sync_request_compaction(1, timeout_s=2.0)
        sess = nh.get_noop_session(1)
        for i in range(20):
            nh.sync_propose(sess, f"k{i}=v{i}".encode())
        nh.sync_request_snapshot(1)
        nh.sync_request_compaction(1)  # completes now
    finally:
        close_all(hosts)


# ---------------------------------------------------------------------------
# event listeners + metrics
# ---------------------------------------------------------------------------


class Recorder:
    """Records every listener callback it receives, thread-safely."""

    def __init__(self):
        self.mu = threading.Lock()
        self.calls = []

    def __getattr__(self, name):
        def cb(*args):
            with self.mu:
                self.calls.append((name, args))
        return cb

    def names(self):
        with self.mu:
            return [c[0] for c in self.calls]


def test_event_listeners_fire():
    rec_raft = Recorder()
    rec_sys = Recorder()
    hosts = make_cluster(prefix="evt", raft_listener=rec_raft,
                         system_listener=rec_sys)
    try:
        lead = wait_leader(hosts)
        nh = hosts[lead]
        sess = nh.get_noop_session(1)
        for i in range(10):
            nh.sync_propose(sess, f"k{i}=v{i}".encode())
        nh.sync_request_snapshot(1)
        deadline = time.time() + 5
        while time.time() < deadline:
            if ("leader_updated" in rec_raft.names()
                    and "snapshot_created" in rec_sys.names()):
                break
            time.sleep(0.05)
        assert "leader_updated" in rec_raft.names()
        # events include the campaign-start leader_id=0 update; the elected
        # leader must appear among them
        infos = [a[0] for n, a in rec_raft.calls if n == "leader_updated"]
        assert all(i.shard_id == 1 for i in infos)
        assert any(i.leader_id == lead for i in infos)
        sys_names = rec_sys.names()
        assert "node_ready" in sys_names
        assert "snapshot_created" in sys_names
        assert "log_compacted" in sys_names
        m = nh.metrics()
        assert m.get("raft.leader_updated", 0) >= 1
        assert m.get("snapshot.created", 0) >= 1
        assert m.get("transport.sent", 0) > 0
    finally:
        close_all(hosts)
    # shutdown events delivered before hub close
    assert "node_host_shutting_down" in rec_sys.names()
    assert "node_unloaded" in rec_sys.names()


# ---------------------------------------------------------------------------
# NotifyCommit + ingress guards (rate limiter, bounded queues)
# ---------------------------------------------------------------------------


def test_notify_commit_event_fires():
    addrs = {i: f"nc-{i}" for i in (1, 2, 3)}
    hosts = {}
    for rid, addr in addrs.items():
        nh = NodeHost(NodeHostConfig(raft_address=addr, rtt_millisecond=5,
                                     notify_commit=True))
        nh.start_replica(addrs, False, KVStateMachine, Config(
            shard_id=1, replica_id=rid, election_rtt=10, heartbeat_rtt=1))
        hosts[rid] = nh
    try:
        lead = wait_leader(hosts)
        nh = hosts[lead]
        sess = nh.get_noop_session(1)
        rs = nh.propose(sess, b"nc=1")
        assert rs.committed_event.wait(5.0), "commit notification missing"
        r = rs.wait(5.0)
        assert r.code.name == "COMPLETED"
        sess.proposal_completed()
    finally:
        for h in hosts.values():
            h.close()


def test_rate_limiter_rejects_when_full():
    from dragonboat_tpu.request import RequestDroppedError

    addrs = {1: "rl-1"}
    nh = NodeHost(NodeHostConfig(raft_address="rl-1", rtt_millisecond=5),
                  auto_run=False)   # engine stopped: nothing drains
    nh.start_replica(addrs, False, KVStateMachine, Config(
        shard_id=1, replica_id=1, election_rtt=10, heartbeat_rtt=1,
        max_in_mem_log_size=256))
    try:
        node = nh.nodes[1]
        sess = nh.get_noop_session(1)
        with pytest.raises(RequestDroppedError):
            for _ in range(64):
                node.propose(sess, b"x" * 64, 100)
        assert node.rate_limiter.rate_limited()
    finally:
        nh.close()


def test_proposal_queue_bound():
    from dragonboat_tpu.request import RequestDroppedError
    from dragonboat_tpu.server.settings import soft

    addrs = {1: "qb-1"}
    nh = NodeHost(NodeHostConfig(raft_address="qb-1", rtt_millisecond=5),
                  auto_run=False)
    nh.start_replica(addrs, False, KVStateMachine, Config(
        shard_id=1, replica_id=1, election_rtt=10, heartbeat_rtt=1))
    try:
        node = nh.nodes[1]
        sess = nh.get_noop_session(1)
        with pytest.raises(RequestDroppedError):
            for _ in range(soft.incoming_proposal_queue_length + 8):
                node.propose(sess, b"q", 100)
    finally:
        nh.close()


# ---------------------------------------------------------------------------
# quiesce at scale: thousands of idle shards ~ free (README.md:50 of the
# reference — "idle groups are approximately free"; quiesce.go:36)
# ---------------------------------------------------------------------------


def test_quiesce_scale_idle_shards_are_free():
    """200 idle single-replica shards on one host: once quiesced, the
    engine finds NO step work (run_once() == 0) and terms freeze — idle
    shards cost ticks only, mirroring the reference's headline claim."""
    shards = tuple(range(1, 201))
    nh = NodeHost(NodeHostConfig(raft_address="qsc-1", rtt_millisecond=2),
                  auto_run=False)
    try:
        for sid in shards:
            nh.start_replica({1: "qsc-1"}, False, KVStateMachine, Config(
                shard_id=sid, replica_id=1, election_rtt=5, heartbeat_rtt=1,
                quiesce=True))
        # elect every shard (single member: first election tick wins)
        deadline = time.time() + 30
        while time.time() < deadline:
            nh.tick_all()
            nh.run_once()
            if all(nh.get_leader_id(s)[1] for s in shards):
                break
        assert all(nh.get_leader_id(s)[1] for s in shards)

        def drive(rs, deadline_s=10):
            # auto_run=False: nothing steps the nodes, so the test drives
            # the engine until the proposal future completes
            end = time.time() + deadline_s
            while time.time() < end and not rs._event.is_set():
                nh.tick_all()
                nh.run_once()
            assert rs._event.is_set(), "proposal never completed"
            return rs

        s = nh.get_noop_session(1)
        drive(nh.propose(s, b"w=1"))
        # idle: tick until every shard enters quiesce (threshold ~50 ticks)
        for _ in range(80):
            nh.tick_all()
            nh.run_once()
        assert all(n.qs.quiesced() for n in nh.nodes.values()), \
            f"{sum(n.qs.quiesced() for n in nh.nodes.values())}/200 quiesced"
        terms = {sid: n.peer.raft.term for sid, n in nh.nodes.items()}
        # quiesced ticks generate no step work
        steps = 0
        for _ in range(30):
            nh.tick_all()
            steps += nh.run_once()
        assert steps == 0, f"quiesced shards still produced {steps} steps"
        assert terms == {sid: n.peer.raft.term for sid, n in nh.nodes.items()}
        # and activity on one shard wakes exactly that shard
        drive(nh.propose(nh.get_noop_session(7), b"wake=1"))
        assert not nh.nodes[7].qs.quiesced()
        assert nh.nodes[8].qs.quiesced()
    finally:
        nh.close()

"""What the ``fleet`` deployment (256 shards x 3 replicas on one chip,
``benchmark/configs/fleet.json``) forced in the program, held on the CPU:
admission that waits for no round, one ``inject_rows`` program for whatever
queued between two rounds, the books of an admission removed before any round
took it, the width counters, and a fleet in small built by the benchmark's
own deployment code."""

import contextlib
import json
import shutil
import tempfile
import threading
import time

import numpy as np
import pytest

from dragonboat_tpu import raftpb as pb, telemetry, tracing
from dragonboat_tpu.config import Config, ExpertConfig, MeshSpec, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.raftpb import MessageType as MT

from test_nodehost import KVStateMachine

#: an ``add_shard`` behind a round waited 0.2-0.4 s on the chip machine and
#: for ever behind a blocked one; one that waits for none returns in
#: milliseconds.  The limit is for a loaded test box
ADMIT_LIMIT_S = 5.0


def wait_for(cond, timeout_s):
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def registry(*prefixes):
    return {k: v for k, v in telemetry.GLOBAL.snapshot().items()
            if k.startswith(prefixes)}


def grew(before, *prefixes):
    return {k: v - before.get(k, 0) for k, v in registry(*prefixes).items()}


def host(prefix, rid=1, auto_run=True, mesh=None, capacity=16):
    return NodeHost(NodeHostConfig(
        raft_address=f"{prefix}-{rid}", rtt_millisecond=5,
        expert=ExpertConfig(mesh=mesh, kernel_log_cap=256,
                            kernel_capacity=capacity, kernel_apply_batch=16,
                            kernel_compaction_overhead=16)),
        auto_run=auto_run)


def start(nh, prefix, sid, rid=1, mesh=False, election_rtt=10):
    nh.start_replica(
        {i: f"{prefix}-{i}" for i in (1, 2, 3)}, False, KVStateMachine,
        Config(shard_id=sid, replica_id=rid, election_rtt=election_rtt,
               heartbeat_rtt=2, device_resident=not mesh, mesh_resident=mesh))


@contextlib.contextmanager
def blocked_round(eng):
    """The engine's next dispatch stops inside its round, the engine lock
    held, until the block is left."""
    entered, release = threading.Event(), threading.Event()
    real = eng._kernel_call

    def call(staging):
        entered.set()
        release.wait(60)
        return real(staging)

    eng._kernel_call = call
    try:
        assert entered.wait(30), "no round came"
        yield
    finally:
        eng._kernel_call = real
        release.set()


# -- (a) admission does not wait for a round ----------------------------------

@pytest.mark.parametrize("mesh", [False, True], ids=["kernel", "mesh"])
def test_add_shard_returns_while_a_round_holds_the_engine_lock(mesh):
    """``start_replica`` of a second shard while the engine's round is stuck
    in its dispatch: the call comes back, the replica is registered
    (``by_shard``) and no round has seen it; the round after the stuck one
    injects it."""
    prefix = f"flt-a{int(mesh)}-{time.monotonic_ns()}"
    spec = (MeshSpec(name=prefix, g_size=2, replicas=3, n_local=4)
            if mesh else None)
    nh = host(prefix, mesh=spec)
    try:
        start(nh, prefix, 1, mesh=mesh)
        eng = nh.mesh_engine if mesh else nh.kernel_engine
        key = (lambda sid: (sid, 1)) if mesh else (lambda sid: sid)
        with blocked_round(eng):
            assert not eng.mu.acquire(blocking=False), "the round let go"
            caller = threading.Thread(
                target=start, args=(nh, prefix, 2), kwargs={"mesh": mesh})
            t0 = time.monotonic()
            caller.start()
            caller.join(ADMIT_LIMIT_S)
            assert not caller.is_alive(), (
                f"start_replica still waits after {ADMIT_LIMIT_S} s: "
                "add_shard is behind the round's lock")
            took = time.monotonic() - t0
            node = eng.by_shard[key(2)]
            assert node.engine is eng and node.lane >= 0
            assert node.lane not in eng.nodes        # no round saw it yet
            assert node.lane in eng._admitting
            if mesh:
                assert eng._dispatch.cut[node.lane].all()   # still cut
        assert wait_for(lambda: eng.nodes.get(node.lane) is node
                        and not eng._pending_inject, 30)
        assert not eng._admitting
        if mesh:
            assert wait_for(lambda: not eng._dispatch.cut[node.lane].any(), 10)
        assert took < ADMIT_LIMIT_S
    finally:
        nh.close()


# -- (b) one program for what queued between two rounds -----------------------

def test_admissions_between_two_rounds_are_one_inject_rows_program():
    """Five ``start_replica`` calls with no round between them, then one
    round: one ``_flush_injections`` batch of five rows (one ``inject_rows``
    call, timed once), every lane visible and live.  A heartbeat
    that arrived for one of them meanwhile is staged by that round, after
    the injection: the lane follows its sender at the message's term (staged
    against the empty row, the injection would have wiped it)."""
    from dragonboat_tpu import capacity

    prefix = f"flt-b-{time.monotonic_ns()}"
    names = ("engine_inject_rows", "engine_inject_flush_us.",
             "engine_add_shard_lock_us.count")
    nh = host(prefix, auto_run=False)
    try:
        before = registry(*names)
        calls0 = capacity.TRACKER.snapshot().get(
            "inject_rows", {}).get("calls", 0)
        for sid in range(1, 6):
            start(nh, prefix, sid, election_rtt=1000)
        eng = nh.kernel_engine
        assert not eng.nodes and len(eng._admitting) == 5
        assert len(eng.by_shard) == 5 and len(eng._free) == 16 - 5
        node = eng.by_shard[2]
        node.handle_message(pb.Message(
            type=MT.HEARTBEAT, from_=2, to=1, shard_id=2, term=5))
        assert eng.step_all()
        flushed = grew(before, *names)
        assert flushed.pop("engine_inject_flush_us.sum") > 0
        assert flushed == {
            "engine_inject_rows": 5, "engine_inject_flush_us.count": 1,
            "engine_add_shard_lock_us.count": 5}
        assert capacity.TRACKER.snapshot()["inject_rows"]["calls"] \
            == calls0 + 1
        assert sorted(eng.nodes) == sorted(
            n.lane for n in eng.by_shard.values())
        assert not eng._admitting and not eng._pending_inject
        assert nh.get_leader_id(2) == (2, True)
        state = eng.state
        assert int(np.asarray(state.term)[node.lane]) == 5
        others = [n.lane for sid, n in eng.by_shard.items() if sid != 2]
        assert (np.asarray(state.term)[others] < 5).all()
    finally:
        nh.close()


# -- (c) an admission removed before any round took it ------------------------

def test_remove_shard_of_a_queued_admission_leaves_the_books_consistent():
    prefix = f"flt-c-{time.monotonic_ns()}"
    nh = host(prefix, auto_run=False, capacity=4)
    try:
        start(nh, prefix, 1)
        eng = nh.kernel_engine
        eng.step_all()          # (a round of injections alone reports none)
        assert set(eng.nodes) == {eng.by_shard[1].lane}
        start(nh, prefix, 2)
        lane = eng.by_shard[2].lane
        flushes = registry("engine_inject_flush_us.count")
        nh.stop_replica(2)                       # no round in between

        def consistent():
            live = {n.lane for n in eng.by_shard.values()}
            assert set(eng.nodes) | set(eng._admitting) == live
            assert sorted(eng._free + list(live)) == list(range(4))
            assert set(np.nonzero(eng._kind_np.any(1))[0]) == set(eng.nodes)

        assert 2 not in eng.by_shard and lane in eng._free
        assert not eng._admitting and not eng._pending_inject
        consistent()
        nh.tick_all()
        assert eng.step_all()                    # drains the removal log
        assert not eng._removed_nodes
        assert grew(flushes, "engine_inject_flush_us.count") == {
            "engine_inject_flush_us.count": 0}   # nothing was injected
        # the lane is handed out again, and a removal AFTER the take
        # clears what the take wrote
        start(nh, prefix, 3)
        assert eng.by_shard[3].lane == lane
        eng.step_all()
        assert eng.nodes[lane] is eng.by_shard[3]
        assert eng._kind_np[lane].any()
        consistent()
        nh.stop_replica(3)
        assert not eng._kind_np[lane].any() and lane not in eng.nodes
        consistent()
        # full: the fifth admission of four lanes falls back to the host
        for sid in (4, 5, 6, 7):
            start(nh, prefix, sid)
        assert len(eng.by_shard) == 4 and not eng._free
        assert nh.nodes[7].peer is not None      # host-resident
        consistent()
    finally:
        nh.close()


def test_mesh_remove_replica_of_a_queued_admission_frees_its_group_lane():
    prefix = f"flt-cm-{time.monotonic_ns()}"
    spec = MeshSpec(name=prefix, g_size=2, replicas=3, n_local=4)
    nh = host(prefix, auto_run=False, mesh=spec)
    try:
        start(nh, prefix, 1, mesh=True)
        eng = nh.mesh_engine
        lanes = len(eng._free_lanes)
        row = eng.by_shard[(1, 1)].lane
        assert row in eng._admitting and 1 in eng._lane_of
        nh.stop_replica(1)
        assert not eng._admitting and not eng.by_shard and not eng._lane_of
        assert len(eng._free_lanes) == lanes + 1
        assert not eng._members and not eng._mirrors and not eng.nodes
        assert eng._dispatch.cut[row].all()
        start(nh, prefix, 1, mesh=True)          # and back
        eng.step_all()
        assert eng.nodes[row] is eng.by_shard[(1, 1)]
        assert eng._members[1] == {1: eng.nodes[row]}
        assert not eng._dispatch.cut[row].any()
    finally:
        nh.close()


# -- (e) the width of a round --------------------------------------------------

def test_round_lane_counter_and_records_read_what_the_rounds_took():
    """Three lanes admitted, ticked through a few rounds: the counter grows
    by what the engine's round records carry (the lanes staged ride the
    record alone), and a tick round of three live lanes stages none and
    processes three."""
    prefix = f"flt-e-{time.monotonic_ns()}"
    names = ("engine_round_lanes", "engine_round_us.count{phase=total}")
    nh = host(prefix, auto_run=False)
    try:
        before = registry(*names)
        for sid in (1, 2, 3):
            start(nh, prefix, sid)
        eng = nh.kernel_engine
        rounds = 0
        for _ in range(40):                      # past an election timeout
            nh.tick_all()
            eng._tick_floor_us = 0               # the test's ticks are due
            rounds += bool(eng.step_all())
        mine = [r for r in tracing.ROUNDS.rounds()
                if r["engine"] == eng._round.engine]
        assert len(mine) == rounds >= 40
        assert grew(before, *names) == {
            "engine_round_lanes{what=processed}":
                sum(r["lanes_processed"] for r in mine),
            "engine_round_us.count{phase=total}": rounds}
        # lanes that campaign (a term change) are processed; never more
        # than hold a replica
        assert max(r["lanes_processed"] for r in mine) == 3
        # the three admissions dirtied their lanes for the round that
        # injected them; a lane a round processed stages again in the next
        assert mine[0]["lanes_staged"] == 3
        assert all(r["lanes_staged"] <= 3 for r in mine)
        nh.stop_replica(2)
        assert len(eng.nodes) == 2
    finally:
        nh.close()


# -- (d) a fleet in small, built by the benchmark's deployment code -----------

def test_a_small_fleet_elects_is_placed_and_agrees_with_the_reference():
    """32 shards x 3 replicas through ``benchmark/deployment.py`` on the CPU:
    96 admissions from three threads, 32 elections, leaders placed
    11 / 11 / 10, two seconds of the seeded ``write16`` load, and the
    benchmark's own checks (every acknowledged write on all 96 state
    machines, read-backs, hashes, fsyncs) against its dict reference."""
    import jax

    from benchmark import run
    from benchmark.deployment import Deployment, load_json

    lines = []

    def say(**fields):
        lines.append(fields)

    bench, entry = run.load_cell("fleet.write16")
    cfg = load_json("configs", "fleet")
    traffic = load_json("traffic", entry["traffic"])
    traffic = {**traffic, **traffic["rehearsal"]}
    root = tempfile.mkdtemp(prefix="fleet-small-")
    keys = ("engine_inject_rows", "engine_inject_flush_us.count",
            "engine_add_shard_lock_us.sum",
            "nodehost_start_replica_us.sum{phase=total}")
    before = registry(*keys)
    dep = None
    said, run.say = run.say, say
    try:
        dep = Deployment(cfg, jax.devices(), root, 32, None, say)
        deployed = next(x for x in lines if x.get("phase") == "deployed")
        assert deployed["shards_led_by_host"] == {1: 11, 2: 11, 3: 10}
        assert [len(e.nodes) for e in dep.engines] == [32, 32, 32]
        cell = run.Cell(bench, entry, cfg, traffic, root, True,
                        run.CompileLog())
        result, _ = run.run_episode(dep, cell, 2**31 + 31, 2.0, False, None,
                                    {}, 0, None)
        drained = next(x for x in lines if x.get("phase") == "drained")
        checks = {x["check"]: x for x in lines if x.get("phase") == "check"}
    finally:
        run.say = said
        if dep is not None:
            dep.close()
        shutil.rmtree(root, ignore_errors=True)
    assert result["correct"] is True and result["failed"] == 0, checks
    assert result["attempted"] > 0
    assert drained["leaders_moved_since_warmup"] == 0
    got = grew(before, *keys)
    assert got["engine_inject_rows"] == 96
    assert got["engine_inject_flush_us.count"] <= 96
    # admission is no part of starting a replica any more
    assert got["engine_add_shard_lock_us.sum"] < 0.1 * got[
        "nodehost_start_replica_us.sum{phase=total}"]
    json.dumps(result)


def test_fleet_configuration_states_what_the_issue_fixed():
    from benchmark.deployment import load_json

    cfg = load_json("configs", "fleet")
    ref = load_json("configs", "upstream-48")
    assert (cfg["shards"], cfg["replicas"], cfg["engine"]) == (256, 3,
                                                                "kernel")
    assert cfg["shards"] * cfg["replicas"] == 768
    for same in ("raft", "guarantees", "state_machine", "logdb",
                 "message_delay_ms", "step_entries", "step_programs",
                 "capture_seconds", "start_hosts_in_parallel", "expert"):
        assert cfg[same] == ref[same], same
    assert "warm_row_fetch" not in cfg
    assert cfg["reduced"] == ["servers", "shards"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["rehearsal"] == {"shards": 6}
    assert cfg["chips"]["count"] == 1


# -- what 256 engine-driven shards a host exposed: the step workers -----------

def test_step_workers_leave_engine_driven_nodes_to_the_engine():
    """A host's step workers used to list and call every node of the host
    on every wake-up, and every message, proposal and apply woke all of
    them: with 256 engine-driven shards a host that was most of a 1.5 s
    engine round (PERF.md, PR 31).  A worker's share holds host-resident
    nodes only, an engine-driven node's no-op ``step`` is never called, and
    a host-resident shard beside them is still stepped and serves."""
    from dragonboat_tpu.engine.kernel_engine import KernelNode
    from test_kernel_engine import propose_retry
    from test_nodehost import wait_leader

    prefix = f"flt-w-{time.monotonic_ns()}"
    addrs = {i: f"{prefix}-{i}" for i in (1, 2, 3)}
    calls = []
    real = KernelNode.step
    KernelNode.step = lambda self: calls.append(self.shard_id) or False
    hosts = {}
    try:
        for rid in addrs:
            hosts[rid] = nh = host(prefix, rid)
            for sid in (1, 2, 3, 4):
                nh.start_replica(addrs, False, KVStateMachine, Config(
                    shard_id=sid, replica_id=rid, election_rtt=10,
                    heartbeat_rtt=2, device_resident=sid != 4))
        nh = hosts[1]
        shares, driven = nh._node_views()
        assert sorted(n.shard_id for n in driven) == [1, 2, 3]
        assert [n.shard_id for share in shares for n in share] == [4]
        assert shares[4 % nh._num_workers] == [nh.nodes[4]]
        for sid in (1, 4):          # an engine-driven and a host-resident one
            lid = wait_leader(hosts, shard_id=sid, timeout=60)
            propose_retry(hosts[lid], hosts[lid].get_noop_session(sid),
                          b"k=v")
            assert wait_for(lambda: all(h.stale_read(sid, "k") == "v"
                                        for h in hosts.values()), 15)
        assert calls == []
        # the shares follow the host's nodes
        nh.stop_replica(4)
        assert not any(nh._node_views()[0])
    finally:
        KernelNode.step = real
        for h in hosts.values():
            h.close()


def test_a_kick_sets_the_work_event_only_when_it_is_clear():
    """``Event.set`` takes the event's lock whether or not the event is
    set; the engine thread called it once a message and queued there
    behind every other caller."""
    class Counting(threading.Event):
        sets = 0

        def set(self):
            self.sets += 1
            super().set()

    prefix = f"flt-k-{time.monotonic_ns()}"
    nh = host(prefix, auto_run=False)
    try:
        nh._work = work = Counting()
        nh._kick()
        nh._kick()
        assert work.sets == 1 and work.is_set()
        work.clear()
        start(nh, prefix, 1)            # start_replica kicks
        assert work.sets == 2
        nh._send_message(pb.Message(type=MT.HEARTBEAT, from_=1, to=2,
                                    shard_id=1, term=1))
        assert work.sets == 2 and work.is_set()
    finally:
        nh.close()


# -- what 256 lanes a host exposed: a message at a time through the send path -

def spy_on_deliveries(nh, into):
    """Record the size of every message batch host ``nh`` is handed."""
    real = nh.transport.deliver

    def deliver(batch):
        into.append([(m.type, m.shard_id, m.to) for m in batch.requests])
        real(batch)

    nh.transport.deliver = deliver


def test_what_a_round_sends_a_host_leaves_as_one_batch():
    """``_send_all`` of six heartbeats for two hosts: one batch each, in
    the order given, every message on its node's queue; a message for a
    replica nobody registered is dropped and counted.  Sent one by one,
    each of a round's ~430 messages (256 lanes) took the process-wide locks
    of the send path on its own, and three engines resolving at once queued
    on them: 600-900 ms rounds (PERF.md, PR 31)."""
    prefix = f"flt-s-{time.monotonic_ns()}"
    hosts = {rid: host(prefix, rid, auto_run=False) for rid in (1, 2, 3)}
    try:
        for rid, nh in hosts.items():
            for sid in (1, 2, 3, 4):
                start(nh, prefix, sid, rid, election_rtt=1000)
            nh.kernel_engine.step_all()          # injects, sends nothing
        got = {rid: [] for rid in (2, 3)}
        for rid in got:
            spy_on_deliveries(hosts[rid], got[rid])
        eng = hosts[1].kernel_engine
        dropped = hosts[1].events.metrics.snapshot().get(
            "transport.dropped", 0)

        def beat(sid, to):
            return (eng.by_shard[sid], pb.Message(
                type=MT.HEARTBEAT, from_=1, to=to, shard_id=sid, term=3))

        eng._send_all([beat(1, 2), beat(1, 3), beat(2, 2), beat(3, 2),
                       beat(2, 3), beat(4, 2), beat(4, 9)])
        assert got[2] == [[(MT.HEARTBEAT, sid, 2) for sid in (1, 2, 3, 4)]]
        assert got[3] == [[(MT.HEARTBEAT, sid, 3) for sid in (1, 2)]]
        assert hosts[1].events.metrics.snapshot()[
            "transport.dropped"] == dropped + 1
        for rid, sids in ((2, (1, 2, 3, 4)), (3, (1, 2))):
            for sid in sids:
                assert [m.type for m in hosts[rid].nodes[sid].incoming_msgs
                        ] == [MT.HEARTBEAT]
        # one message is still a batch of one
        hosts[1]._send_message(beat(3, 3)[1])
        assert got[3][1:] == [[(MT.HEARTBEAT, 3, 3)]]
        # and a partitioned host sends nothing
        hosts[1]._partitioned = True
        eng._send_all([beat(1, 2)])
        assert len(got[2]) == 1
    finally:
        for nh in hosts.values():
            nh.close()


def test_running_engines_send_a_host_fewer_batches_than_messages():
    """Six shards on three running hosts, elected and written to: the
    batches a host is handed carry several messages (every one was a batch
    of one before)."""
    from test_kernel_engine import propose_retry
    from test_nodehost import wait_leader

    prefix = f"flt-t-{time.monotonic_ns()}"
    hosts = {rid: host(prefix, rid) for rid in (1, 2, 3)}
    got = []
    try:
        for nh in hosts.values():
            spy_on_deliveries(nh, got)
        for rid, nh in hosts.items():
            for sid in range(1, 7):
                start(nh, prefix, sid, rid)
        for sid in range(1, 7):
            lid = wait_leader(hosts, shard_id=sid, timeout=60)
            propose_retry(hosts[lid], hosts[lid].get_noop_session(sid),
                          b"k=v")
        assert wait_for(lambda: max(map(len, got)) > 1, 15), (
            "every batch still carries one message")
        assert sum(map(len, got)) > len(got)
        assert wait_for(lambda: all(
            h.stale_read(sid, "k") == "v"
            for h in hosts.values() for sid in range(1, 7)), 15)
    finally:
        for nh in hosts.values():
            nh.close()

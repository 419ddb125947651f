"""The CPU tests that stand for the cell ``upstream-48-mesh4.write16``: the
mesh deployment built the way ``benchmark/deployment.py`` builds it, small,
on forced host devices, and what that cell found in the engine.

The fault the cell showed on four chips (a linearizable read-back timed out
after the drain, PR 24) was in the rows of the sharded state that hold no
replica yet: the exchange addresses rows by position, so while host 1 had
opened its replicas and hosts 2 and 3 had not (35 s a host at 48 shards),
replica 1's votes and appends landed in the empty rows of its group, which
answered them.  Replica 1 was elected and committed its first entry on
acknowledgements no LogDB held; the rows were overwritten when the real
replicas arrived; and once set-up moved the leader, replica 1 kept an entry
at a committed index that the others never had, and refused every append
after it.  Empty rows are now cut from the mesh (``MeshEngine.__init__``,
``add_shard``, ``remove_replica``)."""

import os
import sys
import time

import jax
import numpy as np
import pytest

from dragonboat_tpu import capacity, raftpb as pb, telemetry, tracing
from dragonboat_tpu.config import (
    Config, ExpertConfig, MeshSpec, NodeHostConfig,
)
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.request import RequestError

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_mesh_engine import (  # noqa: E402
    close_all, make_cluster, propose_retry,
)
from test_digest import shadow_engines  # noqa: E402
from test_nodehost import KVStateMachine, wait_leader  # noqa: E402
from test_round_budget import (  # noqa: E402
    check_round_families, check_round_records, part_us, phase_cluster,
    records_of, round_families,
)

from benchmark import deployment, traffic as gen  # noqa: E402


def hub_msgs() -> dict:
    return {k[len("engine_mesh_hub_msgs{way="):-1]: v
            for k, v in telemetry.GLOBAL.snapshot().items()
            if k.startswith("engine_mesh_hub_msgs{")}


def durability() -> list:
    """Rounds, fsyncs, saves, partitions saved and shared fsyncs so far."""
    snap = telemetry.GLOBAL.snapshot()
    return [snap.get(k, 0) for k in (
        "engine_round_us.count{phase=total}", "logdb.fsync_us.count",
        "logdb.save_parts.count", "logdb.save_parts.sum",
        "logdb.sync_shared")]


def wait_for(cond, timeout_s):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


# -- empty rows ---------------------------------------------------------------

def test_empty_mesh_rows_take_no_part():
    """One replica of three alone on the mesh elects nobody and its group's
    empty rows stay empty; the rows join when their replicas are placed;
    and a row whose replica has left is no member again: the leader with
    one follower gone and the other cut off acknowledges nothing."""
    prefix = f"mshG{time.monotonic_ns()}"
    hosts = {}
    try:
        spec = MeshSpec(name=prefix, g_size=2, replicas=3, n_local=4)
        addrs = {i: f"{prefix}-{i}" for i in (1, 2, 3)}

        def start(rid):
            nh = NodeHost(NodeHostConfig(
                raft_address=addrs[rid], rtt_millisecond=5,
                expert=ExpertConfig(mesh=spec, kernel_log_cap=256,
                                    kernel_apply_batch=16,
                                    kernel_compaction_overhead=16)))
            nh.start_replica(addrs, False, KVStateMachine, Config(
                shard_id=1, replica_id=rid, election_rtt=10,
                heartbeat_rtt=2, mesh_resident=True))
            hosts[rid] = nh
            return nh

        eng = start(1).mesh_engine
        rows = {rid: eng._row(eng._lane_of[1], rid) for rid in (1, 2, 3)}
        # the round that takes the admission heals the row (add_shard
        # itself waits for no round)
        assert wait_for(lambda: not eng._dispatch.cut[rows[1]].any(), 10)
        assert eng._dispatch.cut[rows[2]].all()
        assert eng._dispatch.cut[rows[3]].all()
        # several election timeouts (10-20 ticks of 5 ms or a round each)
        time.sleep(3.0)
        assert hosts[1].get_leader_id(1) == (0, False)
        with eng.mu:
            term = np.asarray(eng.state.term)
            vote = np.asarray(eng.state.vote)
            last = np.asarray(eng.state.last)
        assert term[rows[1]] > 0, "replica 1 never campaigned: wait longer"
        for rid in (2, 3):
            assert (term[rows[rid]], vote[rows[rid]], last[rows[rid]]) == (
                0, 0, 0), f"the empty row of replica {rid} answered"

        start(2), start(3)
        assert wait_for(lambda: not any(eng._dispatch.cut[r].any()
                                        for r in rows.values()), 10)
        lid = wait_leader(hosts, timeout=60)
        propose_retry(hosts[lid], hosts[lid].get_noop_session(1), b"a=1")
        assert wait_for(lambda: all(h.stale_read(1, "a") == "1"
                                    for h in hosts.values()), 15)

        gone, cut_off = [r for r in hosts if r != lid]
        hosts.pop(gone).close()
        assert eng._dispatch.cut[rows[gone]].all()
        with eng.mu:
            left_at = int(np.asarray(eng.state.last)[rows[gone]])
        hosts[cut_off].partition_node()
        with pytest.raises(RequestError):
            hosts[lid].sync_propose(hosts[lid].get_noop_session(1), b"b=2",
                                    timeout_s=3)
        with eng.mu:
            assert int(np.asarray(eng.state.last)[rows[gone]]) == left_at
        hosts[cut_off].restore_partitioned_node()
        lid = wait_leader(hosts, timeout=60)
        propose_retry(hosts[lid], hosts[lid].get_noop_session(1), b"c=3")
        assert wait_for(lambda: all(h.stale_read(1, "c") == "3"
                                    for h in hosts.values()), 15)
    finally:
        close_all(hosts)


# -- the cell, small ----------------------------------------------------------

PAUSE_S = 3.0
SHARDS = 3
WRITE_S = 3.0
READ_LIMIT_S = 10.0


@pytest.fixture
def cell(monkeypatch, tmp_path):
    """``benchmark/deployment.py``'s own deployment of the cell's
    configuration at three shards: leaders awaited, then placed by transfer
    on hosts ``(s-1) % 3 + 1``.  A host opens its replicas ``PAUSE_S``
    after the host before it has opened its last, which stands for the
    35 s a host's 48 replicas take on the chip: long enough for the
    replicas already placed to campaign."""
    class LateHost(deployment.NodeHost):
        def start_replica(self, members, join, create, cfg):
            if cfg.shard_id == 1 and cfg.replica_id > 1:
                time.sleep(PAUSE_S)
            return super().start_replica(members, join, create, cfg)

    monkeypatch.setattr(deployment, "NodeHost", LateHost)
    cfg = dict(deployment.load_json("configs", "upstream-48-mesh4"),
               name=f"cell{time.monotonic_ns()}")
    lines = []
    dep = deployment.Deployment(cfg, jax.devices(), str(tmp_path), SHARDS,
                                None, lambda **kw: lines.append(kw))
    try:
        yield dep, lines[-1]
    finally:
        dep.close()


def test_mesh_cell_reads_back_after_leader_placement(cell):
    """A few seconds of 16 writes in flight a shard to the leaders' hosts,
    a drain, then for every shard ONE linearizable read from the leader's
    host and ONE from a follower's, each under one limit with no retry:
    the values are the acknowledged ones, the replicas' state machines
    hash alike, and nothing met the host transport but the followers'
    forwarded reads."""
    dep, deployed = cell
    assert deployed["shards_led_by_host"] == {1: 1, 2: 1, 3: 1}
    assert set(deployed["link_classes"].values()) == {"resident"}
    traffic = deployment.load_json("traffic", "write16")
    gen.validate(traffic)
    hub0 = hub_msgs()

    assert {h.logdb.name() for h in dep.hosts.values()} == {"sharded-tan-1"}
    d0 = durability()
    load = gen.Load(dep, traffic, gen.client_streams(
        traffic, 2**31 + 27, dep.shards, 0))
    load.start()
    time.sleep(WRITE_S)
    records = load.join(30.0)
    # the one engine saves its three hosts' logs in turn: a round is at
    # most one fsync a LogDB, each save one partition, none shared
    rounds, fsyncs, saves, parts, shared = (
        b - a for a, b in zip(d0, durability()))
    assert 0 < fsyncs <= 3 * rounds, (fsyncs, rounds)
    assert saves == parts == fsyncs and shared == 0
    assert records and all(r.status == gen.OK for r in records)
    acked = {(r.shard, r.key): r.value for r in records}
    assert len(acked) == len(records)           # every write a new key
    assert {r.shard for r in records} == set(dep.shards)
    assert hub_msgs() == hub0, "writes to leaders met the host transport"

    for sid in dep.shards:
        lead = dep.leader_host(sid)
        assert lead == (sid - 1) % 3 + 1        # nothing moved the leaders
        key = max(k for s, k in acked if s == sid)
        for rid in (lead, lead % 3 + 1):
            assert dep.hosts[rid].sync_read(
                sid, key, timeout_s=READ_LIMIT_S) == acked[(sid, key)], (
                sid, rid)
    after = hub_msgs()
    assert after["sent"] == hub0["sent"]
    assert after["stray_dropped"] == hub0["stray_dropped"]
    # a follower's read is a READ_INDEX over and a READ_INDEX_RESP back
    assert after["read_forward"] - hub0["read_forward"] == 2 * len(dep.shards)

    assert wait_for(lambda: all(len(set(dep.sm_hashes(sid))) == 1
                                for sid in dep.shards), 15)
    for rid in dep.hosts:
        for (sid, key), value in acked.items():
            assert dep.replica_value(rid, sid, key) == value
    rows = capacity.TRACKER.snapshot()["serve_step"]
    assert rows["retraces"] == 0


# -- what the mesh adds to the tracing ----------------------------------------

def test_mesh_round_phases_crossings_and_counter():
    """A mesh engine's rounds land in ``engine_round_us{phase}`` like a
    serial engine's; a busy round crosses the boundary as ``round_up`` and
    ``round_down`` and a changed cut mask as ``cut_up``, inside ``upload``;
    its records and annotations carry the mesh engine's label; the serve
    entry compiled once; and ``engine_mesh_hub_msgs`` stays put while the
    links are resident, counts a cut link's traffic as ``sent`` and a hub
    copy of a resident link's message as ``stray_dropped``."""
    from dragonboat_tpu import tracing

    prefix = f"mshT{time.monotonic_ns()}"
    hosts = make_cluster(prefix)
    try:
        lid = wait_leader(hosts, timeout=60)
        nh, eng = hosts[lid], hosts[lid].mesh_engine
        sess = nh.get_noop_session(1)
        propose_retry(nh, sess, b"warm=up")
        assert eng._round.engine == f"mesh:{prefix}"
        # what the entry returns is spelled as what was placed, so its own
        # outputs do not compile it a second time (the count itself is of
        # the process: an earlier test's engine may have compiled it)
        with eng.mu:
            placed = eng.cluster.sharding()
            assert all(x.sharding == placed for x in jax.tree.leaves(
                (eng._resident, eng._dispatch._box,
                 eng.state, eng._dispatch.box)))
            # the serve entry takes the resident state's three arrays, the
            # carried inbox, the upload and the cut mask, and returns the
            # state, the inbox and the download: at most 8 each way
            assert eng._dispatch.entry_arrays == (6, 5)
            snap = telemetry.GLOBAL.snapshot()
            assert snap["engine_entry_arrays{dir=in}"] == 6
            assert snap["engine_entry_arrays{dir=out}"] == 5
        assert eng._cap_entries["serve_step"].stats()["compiles"] <= 1

        def snap():
            with eng.mu:            # between rounds, not inside one
                s = telemetry.GLOBAL.snapshot()
                return ({k: v for k, v in s.items()
                         if k.startswith("engine_round_us.count{")},
                        capacity.METER.counts(), hub_msgs())

        rounds0, meter0, hub0 = snap()
        for i in range(5):
            propose_retry(nh, sess, f"k{i}=v{i}".encode())
        rounds1, meter1, hub1 = snap()
        grown = {k: rounds1[k] - rounds0.get(k, 0) for k in rounds1}
        n = grown["engine_round_us.count{phase=total}"]
        assert n >= 5
        for phase in ("stage", "upload", "fetch", "resolve", "save",
                      "finish"):
            assert grown[f"engine_round_us.count{{phase={phase}}}"] == n
        assert meter1["round_up"] - meter0.get("round_up", 0) == n
        assert meter1["round_down"] - meter0.get("round_down", 0) == n
        assert meter1.get("cut_up", 0) == meter0.get("cut_up", 0)
        assert hub1 == hub0
        mine = [r for r in tracing.ROUNDS.rounds()
                if r["engine"] == f"mesh:{prefix}"]
        assert len(mine) >= n

        # a cut link: its traffic is handed to the host transport, and the
        # changed mask goes up once, inside a round's upload
        frid = next(r for r in hosts if r != lid)
        eng.set_link_hub_served(eng.by_shard[(1, lid)], frid, True)
        propose_retry(nh, sess, b"during=cut")
        assert wait_for(lambda: hosts[frid].stale_read(1, "during") == "cut",
                        30)
        _r, meter2, hub2 = snap()
        assert hub2["sent"] > hub1["sent"]
        assert hub2["stray_dropped"] == hub1["stray_dropped"]
        assert meter2["cut_up"] == meter1.get("cut_up", 0) + 1
        eng.set_link_hub_served(eng.by_shard[(1, lid)], frid, False)
        propose_retry(nh, sess, b"post=heal")

        # a hub copy of a resident link's message is a stray: dropped at
        # the gate, and counted
        _r, _m, hub3 = snap()
        hosts[frid]._handle_message_batch(pb.MessageBatch(
            requests=(pb.Message(type=pb.MessageType.HEARTBEAT, to=frid,
                                 from_=lid, shard_id=1, term=1),),
            deployment_id=hosts[frid].config.deployment_id,
            source_address=f"{prefix}-{lid}"))
        assert hub_msgs()["stray_dropped"] == hub3["stray_dropped"] + 1
    finally:
        close_all(hosts)



def test_mesh_collection_is_one_crossing_and_one_carried_array(monkeypatch):
    """The mesh engine inherits the collection: every tenth round ONE
    program over the sharded state (the carried box's sender ids sliced
    inside it), ONE ``digest_down`` crossing, ONE carried ``[G, 17]`` array
    sharded along G like the state; at most the resident arrays + 2 in and
    2 out; ``finish.collect`` in every round record's parts, above 0 only in
    the rounds that collected; and at every collection the reports and the
    next carry equal the three programs' (``test_digest.Shadow``)."""
    prefix = f"mshC{time.monotonic_ns()}"
    shadows = shadow_engines(monkeypatch)
    hosts = make_cluster(prefix)
    try:
        lid = wait_leader(hosts, timeout=60)
        nh, eng = hosts[lid], hosts[lid].mesh_engine
        sess = nh.get_noop_session(1)
        propose_retry(nh, sess, b"warm=up")
        assert wait_for(lambda: eng._health_seq >= 1, 30)

        def snap():
            with eng.mu:            # between rounds, not inside one
                return (eng._round._seq, eng._health_seq, eng._inv_seq,
                        capacity.METER.counts().get("digest_down", 0))

        seq0, health0, inv0, down0 = snap()
        for i in range(12):
            propose_retry(nh, sess, f"k{i}=v{i}".encode())
        assert wait_for(lambda: eng._health_seq >= health0 + 2, 30)
        seq1, health1, inv1, down1 = snap()
        recs = [r for r in records_of(eng, seq0) if r["seq"] <= seq1]
        assert len(recs) == seq1 - seq0
        assert all(set(r["parts"]) == set(tracing.ROUND_PARTS) for r in recs)
        collected = sum(r["parts"]["finish.collect"] > 0 for r in recs)
        # (the engines of the process share the meter: this one's are its
        # health ticks)
        assert collected == health1 - health0 == inv1 - inv0 >= 2
        assert down1 - down0 >= collected
        assert collected <= len(recs) // 10 + 1
        check_round_records(recs)
        with eng.mu:
            placed = eng.cluster.sharding()
            assert eng._digest.shape == (eng.capacity, 17)
            assert eng._digest.sharding == placed
            assert all(x.sharding == placed for x in jax.tree.leaves(
                (eng._health_digest, eng._inv_digest)))
            resident = len(jax.tree.leaves(eng._resident))
            assert eng.digest_arrays == (resident + 2, 2)
            stats = eng._cap_entries["fleet_digest"].stats()
            assert stats["compiles"] <= 1 and stats["retraces"] == 0
            fleet, health, inv = (eng.last_fleet, eng.last_health,
                                  eng.last_invariants)
            shadow = shadows[eng]
            assert not shadow.mismatches, shadow.mismatches[:3]
            assert shadow.compared == eng._health_seq >= 3
        assert fleet["occupied"] == 3 and fleet["role_count"]["leader"] == 1
        assert health["leaderless_now"] == 0 and inv["total"] == 0
        assert inv["checked"] == 3
        assert not {"fleet_down", "health_down", "invariants_down"} \
            & set(capacity.METER.counts())
    finally:
        close_all(hosts)


@pytest.mark.parametrize("depth", [0, 1])
def test_mesh_rounds_read_their_parts_and_mark_only_what_they_sent(depth):
    """The mesh engine inherits the round timer's parts: one engine holds
    the three hosts' futures, so ``finish.apply`` and ``finish.ack`` both
    read above 0 on it and stay inside ``finish``; while every link is
    resident a round hands the host transport nothing and makes no mark;
    with one link cut its REPLICATEs leave by the hub and the round that
    sent them marks ``replicates_out``."""
    prefix = f"mshP{depth}x{time.monotonic_ns()}"
    before = round_families()
    hosts = phase_cluster(prefix, depth, mesh=MeshSpec(
        name=prefix, g_size=2, replicas=3, n_local=4))
    try:
        lid = wait_leader(hosts, timeout=60)
        nh, eng = hosts[lid], hosts[lid].mesh_engine
        sess = nh.get_noop_session(1)
        propose_retry(nh, sess, b"warm=up")
        seq = eng._round._seq
        for i in range(8):
            propose_retry(nh, sess, f"k{i}=v{i}".encode())
        resident = records_of(eng, seq)
        frid = next(r for r in hosts if r != lid)
        eng.set_link_hub_served(eng.by_shard[(1, lid)], frid, True)
        seq = eng._round._seq
        propose_retry(nh, sess, b"during=cut")
        assert wait_for(lambda: hosts[frid].stale_read(1, "during") == "cut",
                        30)
        cut = records_of(eng, seq)
    finally:
        close_all(hosts)
    grown = check_round_families(before, round_families())
    for part in tracing.ROUND_PARTS:
        assert grown[f"engine_round_part_us.sum{{part={part}}}"] > 0, part
    check_round_records(resident + cut)
    assert part_us(resident, "finish.apply") > 0
    assert part_us(resident, "finish.ack") > 0
    assert not any(r["marks"] for r in resident)
    assert any("replicates_out" in r["marks"] for r in cut)


# -- the mesh with quiesce on: ``fleet-1k-mesh4`` at its rehearsal's size ------

FLEET_SHARDS, FLEET_BUSY = 12, 3


def test_fleet_mesh_with_quiesce_holds_what_the_mesh_cell_holds(tmp_path):
    """``fleet-1k-mesh4`` as ``benchmark/deployment.py`` builds it, at the
    rehearsal's size (12 groups, 3 written to): while every group sleeps,
    while three are written to and while an idle one is woken by a write,
    what the 48-group mesh holds is held: at most one fsync a LogDB a
    round, the entry's arrays 6 / 5, one upload and one download a round,
    and NOTHING over any ``way`` of ``engine_mesh_hub_msgs``: the quiesce
    word rides the heartbeat lanes of the exchange, and a word on a
    resident link must not also go to the hub."""
    cfg = dict(deployment.load_json("configs", "fleet-1k-mesh4"),
               name=f"fleetcell{time.monotonic_ns()}")
    assert cfg["shard"] == {"quiesce": True} and cfg["engine"] == "mesh"
    mix = deployment.load_json("traffic", "write16-hot96")
    mix = {**mix, **mix["rehearsal"]}
    gen.validate(mix, FLEET_SHARDS)
    busy = gen.active_shards(mix, 2**31 + 39, deployment.wanted_leaders(
        range(1, FLEET_SHARDS + 1), 3))
    assert len(busy) == FLEET_BUSY
    dep = deployment.Deployment(cfg, jax.devices(), str(tmp_path),
                                FLEET_SHARDS, None, lambda **kw: None, busy)
    try:
        eng, = dep.engines
        hub0 = hub_msgs()

        def asleep(n):
            return wait_for(lambda: dep.quiesced_lanes() == n, 90)

        def counts():
            with eng.mu:            # between rounds, not inside one
                meter = capacity.METER.counts()
                return durability(), [meter.get(tag, 0)
                                      for tag in ("round_up", "round_down")]

        # every group asleep: 36 of 36 rows by the one engine's digest
        assert asleep(3 * FLEET_SHARDS), eng.last_fleet
        assert hub_msgs() == hub0, "a quiesce word went to the hub"
        terms = {sid: dep.hosts[1].nodes[sid].node_term()
                 for sid in dep.shards}

        # three groups written to: they wake, the other 27 rows sleep on
        d0, m0 = counts()
        load = gen.Load(dep, mix, gen.client_streams(mix, 2**31 + 39,
                                                     dep.active, 0))
        load.start()
        time.sleep(WRITE_S)
        during = dep.quiesced_lanes()
        records = load.join(30.0)
        d1, m1 = counts()
        rounds, fsyncs, saves, parts, shared = (b - a for a, b in zip(d0, d1))
        assert records and all(r.status == gen.OK for r in records)
        assert {r.shard for r in records} == set(dep.active)
        assert during == 3 * (FLEET_SHARDS - FLEET_BUSY), during
        assert 0 < fsyncs <= 3 * rounds, (fsyncs, rounds)
        assert saves == parts == fsyncs and shared == 0
        assert [b - a for a, b in zip(m0, m1)] == [rounds, rounds]
        assert eng._dispatch.entry_arrays == (6, 5)
        assert hub_msgs() == hub0, "writes to waking groups met the hub"

        # the busy groups idle back to sleep; one idle group is woken by a
        # write after the drain (the benchmark's check (f)) and serves
        assert asleep(3 * FLEET_SHARDS), eng.last_fleet
        sid = dep.idle[0]
        lead = dep.hosts[dep.leader_host(sid)]
        lead.sync_propose(lead.get_noop_session(sid), b"woken=yes",
                          timeout_s=10)
        assert wait_for(lambda: all(
            dep.replica_value(rid, sid, "woken") == "yes"
            for rid in dep.hosts), 15)
        assert hub_msgs() == hub0, "a wake met the hub"
        assert asleep(3 * FLEET_SHARDS), eng.last_fleet
        # nobody campaigned through any of it, and no leader moved
        assert {s: dep.hosts[1].nodes[s].node_term()
                for s in dep.shards} == terms
        assert all(dep.leader_host(s) == dep.wanted[s] for s in dep.shards)
        assert wait_for(lambda: all(len(set(dep.sm_hashes(s))) == 1
                                    for s in dep.shards), 15)
        assert capacity.TRACKER.snapshot()["serve_step"]["retraces"] == 0
    finally:
        dep.close()
        # (its records count up to 36 lanes asleep: not for a later reader
        # of the process's ring, tests/benchmark/test_benchmark_fleet1k_cell)
        tracing.ROUNDS.reset()

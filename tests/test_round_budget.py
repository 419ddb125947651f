"""A round's budget at the device boundary, on the CPU engine: one upload,
one call, one download, nothing compiles after the first round, and the
round lets go of a handful of device arrays, not a ShardState's 45 (each
one let go is a wait for the interpreter).

The tests drive the engine's rounds themselves: they hold the engine lock
(re-entrant; the engine's own worker waits for it), queue client work,
call ``step_all`` and read ``capacity.METER`` / ``capacity.TRACKER`` / the
registry around each round, so every crossing counted is that round's."""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

import jax
import pytest

from dragonboat_tpu import capacity, raftpb as pb, telemetry, tracing
from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_nodehost import KVStateMachine  # noqa: E402

SHARDS = 48
EVERY = 10      # fleet_stats_every: the every-tenth-round collections
COLLECTIONS = {"digest_down"}   # fleet, health, invariants: ONE crossing


def _wait(cond, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


def _host(prefix, shards, depth=0, device=True, root=None, capacity=64):
    """One NodeHost, ``shards`` single-replica shards: a proposal is
    appended, committed, saved and applied in the round that stages it.
    With ``root`` the LogDB is the on-disk default under it."""
    nh = NodeHost(NodeHostConfig(
        raft_address=f"{prefix}-1", rtt_millisecond=5,
        node_host_dir=root or "",
        expert=ExpertConfig(kernel_log_cap=64, kernel_capacity=capacity,
                            fleet_stats_every=EVERY,
                            kernel_pipeline_depth=depth)))
    for sid in range(1, shards + 1):
        nh.start_replica({1: f"{prefix}-1"}, False, KVStateMachine, Config(
            shard_id=sid, replica_id=1, election_rtt=10, heartbeat_rtt=2,
            device_resident=device))
    assert _wait(lambda: all(nh.get_leader_id(sid)[1]
                             for sid in range(1, shards + 1)), 90), \
        "not every shard elected"
    return nh


def _settle(eng):
    """Under the engine lock: run rounds until one finds nothing to do
    (elections, bootstrap config changes and their peer-book uploads
    are behind us)."""
    for _ in range(200):
        if not eng.step_all() and eng._pending_ctx is None:
            return
    raise AssertionError("the engine never went idle")


def _xla_compiles() -> float:
    return sum(v for k, v in telemetry.GLOBAL.snapshot().items()
               if k.startswith("xla_compiles{"))


def _tracked_compiles(eng) -> dict:
    return {name: w.stats()["compiles"]
            for name, w in eng._cap_entries.items()}


@pytest.mark.parametrize("depth", [0, 1])
def test_round_crosses_the_boundary_once_each_way(depth):
    """Over 20 busy rounds ``METER.counts()`` grows by exactly one
    ``round_up`` and one ``round_down`` a round, and by nothing else
    outside the every-tenth-round collections (one of each then)."""
    nh = _host(f"rb-cross{depth}", 4, depth=depth)
    try:
        eng = nh.kernel_engine
        sessions = {sid: nh.get_noop_session(sid) for sid in range(1, 5)}
        with eng.mu:
            _settle(eng)
            states, rounds, seen = [], 0, {}
            for i in range(20):
                for sid, s in sessions.items():
                    states.append(nh.propose(s, f"k{i}={sid}".encode(), 30))
                before = capacity.METER.counts()
                countdown = eng._fleet_countdown
                assert eng.step_all(), f"round {i} found nothing to do"
                after = capacity.METER.counts()
                delta = {t: after[t] - before.get(t, 0) for t in after
                         if after[t] != before.get(t, 0)}
                want = {"round_up": 1}
                if depth == 0 or i > 0:
                    want["round_down"] = 1      # depth 1 retires one late
                if countdown == 1:
                    want.update(dict.fromkeys(COLLECTIONS, 1))
                assert delta == want, f"round {i}: {delta} != {want}"
                rounds += 1
                for t, n in delta.items():
                    seen[t] = seen.get(t, 0) + n
            assert seen["round_up"] == rounds == 20
            assert seen.get("digest_down", 0) == rounds // EVERY
            _settle(eng)                    # depth 1: retire the last step
        for rs in states:
            assert rs.get(30) is not None
    finally:
        nh.close()


@pytest.mark.parametrize("depth", [0, 1])
def test_a_collecting_round_is_one_program_one_array_down_one_carried(depth):
    """Every tenth round the fleet statistics, the health triage and the
    invariant probe run as ONE program: it takes the resident arrays, the
    sender ids and the carried digests and returns one vector and the
    digests (at most the resident arrays + 2 in, 2 out), compiles once, and
    rewrites ONE carried ``[G, 17]`` array.  ``finish.collect`` is in every
    round record's parts: above 0 in the collecting rounds, 0 in the other
    nine of ten."""
    nh = _host(f"rb-collect{depth}", 4, depth=depth)
    try:
        eng = nh.kernel_engine
        sessions = {sid: nh.get_noop_session(sid) for sid in range(1, 5)}
        with eng.mu:
            _settle(eng)
            seq0 = eng._round._seq
            health0, inv0 = eng._health_seq, eng._inv_seq
            states, collecting = [], []
            for i in range(20):
                for sid, s in sessions.items():
                    states.append(nh.propose(s, f"c{i}={sid}".encode(), 30))
                collecting.append(eng._fleet_countdown == 1)
                carried = eng._digest
                assert eng.step_all(), f"round {i} found nothing to do"
                assert (eng._digest is not carried) == collecting[-1]
            recs = records_of(eng, seq0)
            assert len(recs) == 20 and sum(collecting) == 20 // EVERY
            for rec, collected in zip(recs, collecting):
                assert set(rec["parts"]) == set(tracing.ROUND_PARTS)
                assert (rec["parts"]["finish.collect"] > 0) == collected, rec
            check_round_records(recs)
            assert eng._health_seq - health0 == eng._inv_seq - inv0 \
                == 20 // EVERY
            resident = len(jax.tree.leaves(eng._resident))
            assert eng.digest_arrays == (resident + 2, 2)
            assert eng._digest.shape == (eng.capacity, 17)
            assert str(eng._digest.dtype) == "int32"
            stats = eng._cap_entries["fleet_digest"].stats()
            assert stats["compiles"] <= 1 and stats["retraces"] == 0
            assert not {"fleet_stats", "fleet_health", "check_invariants"} \
                & set(eng._cap_entries)
            _settle(eng)
        assert not {"fleet_down", "health_down", "invariants_down"} \
            & set(capacity.METER.counts())
        for rs in states:
            assert rs.get(30) is not None
    finally:
        nh.close()


@pytest.mark.parametrize("depth", [0, 1])
def test_steady_round_is_one_write_and_one_fsync_of_one_log(tmp_path, depth):
    """On a new default directory a round that saves all 48 lanes' entries
    hands its LogDB ONE save that touches one partition and costs one
    fsync on the engine thread: no flush pool, no shared fsync to wait
    for (16 partition flushes through a pool before PR 30)."""
    nh = _host(f"rb-fsync{depth}", SHARDS, depth=depth,
               root=str(tmp_path / "nh"))
    try:
        assert nh.logdb.name() == "sharded-tan-1"
        eng = nh.kernel_engine
        sessions = {sid: nh.get_noop_session(sid)
                    for sid in range(1, SHARDS + 1)}

        def durability():
            snap = telemetry.GLOBAL.snapshot()
            return {k: snap.get(k, 0) for k in (
                "logdb.fsync_us.count", "logdb.save_parts.count",
                "logdb.save_parts.sum", "logdb.sync_shared")}

        with eng.mu:
            _settle(eng)
            states, saves = [], 0
            for i in range(12):
                for sid, s in sessions.items():
                    states.append(nh.propose(s, f"k{i}={sid}".encode(), 30))
                d0 = durability()
                assert eng.step_all(), f"round {i} found nothing to do"
                d1 = durability()
                grew = {k: d1[k] - d0[k] for k in d0}
                assert grew["logdb.fsync_us.count"] <= 1, (i, grew)
                assert grew["logdb.save_parts.count"] == \
                    grew["logdb.save_parts.sum"] == \
                    grew["logdb.fsync_us.count"], (i, grew)
                assert grew["logdb.sync_shared"] == 0, (i, grew)
                saves += grew["logdb.fsync_us.count"]
            _settle(eng)
            assert saves >= 11          # depth 1 saves a round late
        for rs in states:
            assert rs.get(30) is not None
        assert not any(t.name.startswith("tanshard-flush")
                       for t in threading.enumerate())
        assert [c for _i, _t, c in _persisted(nh, SHARDS) if c] == \
            [f"k{i}={SHARDS}".encode() for i in range(12)]
    finally:
        nh.close()


# -- what a round's largest phases are made of --------------------------------

def phase_cluster(prefix, depth, mesh=None):
    """Three NodeHosts with one three-replica shard: an engine each, or
    one mesh engine for the three (``mesh``: its ``MeshSpec``)."""
    addrs = {i: f"{prefix}-{i}" for i in (1, 2, 3)}
    hosts = {}
    try:
        for rid, addr in addrs.items():
            hosts[rid] = nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=5,
                expert=ExpertConfig(
                    mesh=mesh, kernel_log_cap=256, kernel_capacity=8,
                    kernel_apply_batch=16, kernel_compaction_overhead=16,
                    kernel_pipeline_depth=depth)))
            nh.start_replica(addrs, False, KVStateMachine, Config(
                shard_id=1, replica_id=rid, election_rtt=10,
                heartbeat_rtt=2, device_resident=mesh is None,
                mesh_resident=mesh is not None))
    except BaseException:
        for nh in hosts.values():
            nh.close()
        raise
    return hosts


def round_families() -> dict:
    return {k: v for k, v in telemetry.GLOBAL.snapshot().items()
            if k.startswith(("engine_round_us.", "engine_round_cpu_us.",
                             "engine_round_phase_cpu_us.",
                             "engine_round_part_us.",
                             "engine_round_mark_us."))}


def records_of(eng, after_seq=0) -> list:
    return [r for r in tracing.ROUNDS.rounds()
            if r["engine"] == eng._round.engine and r["seq"] > after_seq]


def check_round_families(before: dict, after: dict) -> dict:
    """What the registry's round families grew by between two snapshots
    taken while no engine ran: the six phase CPU times read in some rounds
    (not in all: the timer reads them in about one round an engine per
    40 ms) and never more than the rounds' CPU time, every part summed
    (the rounds that ``engine_round_us{phase=total}`` counts are their
    count), a part inside the phase that holds it, a mark at most once a
    round; -> the growth."""
    grown = {k: v - before.get(k, 0) for k, v in after.items()}
    rounds = grown["engine_round_us.count{phase=total}"]
    assert rounds > 0
    assert grown["engine_round_cpu_us.count"] == rounds
    phase_cpu = [grown[f"engine_round_phase_cpu_us.sum{{phase={p}}}"]
                 for p in tracing.ROUND_PHASES[1:]]
    assert 0 < sum(phase_cpu) <= grown["engine_round_cpu_us.sum"] * (1 + 1e-6)
    assert not [k for k in grown
                if k.startswith(("engine_round_phase_cpu_us.count",
                                 "engine_round_part_us.count"))]
    for part in tracing.ROUND_PARTS:
        assert grown[f"engine_round_part_us.sum{{part={part}}}"] >= 0, part
    for phase in ("stage", "upload", "resolve", "finish"):
        inside = sum(grown[f"engine_round_part_us.sum{{part={p}}}"]
                     for p in tracing.ROUND_PARTS
                     if p.startswith(phase + "."))
        assert 0 < inside <= grown[f"engine_round_us.sum{{phase={phase}}}"]
    for mark in tracing.ROUND_MARKS:
        assert grown.get(f"engine_round_mark_us.count{{mark={mark}}}", 0) \
            <= rounds
    return grown


def check_round_records(recs: list) -> None:
    """Every record: seven parts, each phase's parts inside the phase (a
    record's times are whole microseconds, so to one a reading), and the
    marks in a round's order."""
    for rec in recs:
        entries = rec["phases"]
        us: dict = {}
        for (phase, ts), (_next, end) in zip(entries, entries[1:]):
            us[phase] = us.get(phase, 0) + end - ts
        assert set(rec["parts"]) == set(tracing.ROUND_PARTS)
        slack = len(entries) + len(rec["parts"])
        for phase in ("stage", "upload", "resolve", "finish"):
            inside = sum(wall for p, wall in rec["parts"].items()
                         if p.startswith(phase + "."))
            assert inside <= us.get(phase, 0) + slack, (phase, rec)
        marks = rec["marks"]
        total = entries[-1][1] - rec["t0_us"]
        assert set(marks) <= set(tracing.ROUND_MARKS)
        assert all(0 <= at <= total + 1 for at in marks.values()), rec
        if len(marks) == 2:
            assert marks["replicates_out"] <= marks["responses_out"]


def part_us(recs: list, part: str) -> int:
    return sum(r["parts"][part] for r in recs)


@pytest.mark.parametrize("depth", [0, 1])
def test_a_busy_round_reads_its_parts_where_their_work_ran(depth):
    """Three serial engines under writes to one leader: every part reads
    above 0 where its work ran; ``finish.ack`` only on the engine whose
    host holds the futures; ``finish.apply`` on all three; a leader's
    rounds mark ``replicates_out``, the followers' ``responses_out``; and
    the registry's families close (``check_round_families``)."""
    from test_mesh_engine import propose_retry
    from test_nodehost import wait_leader

    before = round_families()
    hosts = phase_cluster(f"rb-parts{depth}-{time.monotonic_ns()}", depth)
    try:
        lid = wait_leader(hosts, timeout=60)
        nh = hosts[lid]
        sess = nh.get_noop_session(1)
        propose_retry(nh, sess, b"warm=up")
        seqs = {rid: h.kernel_engine._round._seq for rid, h in hosts.items()}
        term = nh.nodes[1]._leader_term_cache
        for i in range(12):
            propose_retry(nh, sess, f"k{i}=v{i}".encode())
        # (on a loaded box a 50 ms election timeout does move the leader:
        # who sent what is held only where it stayed)
        stayed = (wait_leader(hosts, timeout=60) == lid
                  and nh.nodes[1]._leader_term_cache == term)
        recs = {rid: records_of(h.kernel_engine, seqs[rid])
                for rid, h in hosts.items()}
    finally:
        for h in hosts.values():
            h.close()
    grown = check_round_families(before, round_families())
    for part in tracing.ROUND_PARTS:
        assert grown[f"engine_round_part_us.sum{{part={part}}}"] > 0, part
    for rid, mine in recs.items():
        assert mine, rid
        check_round_records(mine)
        assert part_us(mine, "finish.apply") > 0
        if rid == lid:
            assert part_us(mine, "finish.ack") > 0
            assert part_us(mine, "resolve.send") > 0
            assert any("replicates_out" in r["marks"] for r in mine)
        elif stayed:
            assert part_us(mine, "finish.ack") == 0
            assert any("responses_out" in r["marks"] for r in mine)
            assert not any("replicates_out" in r["marks"] for r in mine)
    assert grown["engine_round_mark_us.count{mark=replicates_out}"] > 0
    assert grown["engine_round_mark_us.count{mark=responses_out}"] > 0


def _retired() -> dict:
    snap = telemetry.GLOBAL.snapshot()
    return {path: snap.get(f"engine_retire_lanes{{path={path}}}", 0)
            for path in ("columnar", "per_lane")}


#: what a steady round of single-replica shards, each proposed to once,
#: reads of its download through numpy: no field of it is materialised over
#: all [G] rows (``column_value``) and none is asked of a ``_RoundDown``
#: (the per-lane pass asked 41 times a lane, each followed by a numpy scalar
#: read; the rare classes' handlers still do): the activity mask is one
#: gather of the occupied rows' mask columns, and the candidate rows are
#: gathered and turned into lists ONCE
STEADY_READS = {"column_value": 0, "getitem": 0, "tolist": 1}


@pytest.mark.parametrize("shards", [1, 48, 256])
def test_steady_round_reads_its_download_by_columns(shards, monkeypatch):
    """The output pass reads the download once, never a cell at a time:
    a steady round materialises the same fields of the activity mask and
    turns its candidate rows into lists once at 1, 48 and 256 live lanes,
    all of them retired on the columnar path."""
    from dragonboat_tpu.engine import kernel_engine as ke

    calls = dict.fromkeys(STEADY_READS, 0)
    real_value, real_getitem = ke.column_value, ke._RoundDown.__getitem__

    def column_value(c, packed):
        calls["column_value"] += 1
        return real_value(c, packed)

    def getitem(self, f):
        calls["getitem"] += 1
        return real_getitem(self, f)

    class Retiring(ke._Retiring):
        def __init__(self, lanes, nodes, host, cols):
            calls["tolist"] += 1
            super().__init__(lanes, nodes, host, cols)

    nh = _host(f"rb-cols{shards}", shards, capacity=max(64, shards))
    try:
        eng = nh.kernel_engine
        sessions = [nh.get_noop_session(sid) for sid in range(1, shards + 1)]
        with eng.mu:
            _settle(eng)
            monkeypatch.setattr(ke, "column_value", column_value)
            monkeypatch.setattr(ke._RoundDown, "__getitem__", getitem)
            monkeypatch.setattr(ke, "_Retiring", Retiring)
            for i in range(3):
                states = [nh.propose(s, f"k{i}=v".encode(), 30)
                          for s in sessions]
                calls.update(dict.fromkeys(calls, 0))
                before = _retired()
                assert eng.step_all()
                assert calls == STEADY_READS, f"round {i} at {shards} lanes"
                assert eng._lanes_processed == shards
                after = _retired()
                assert after["columnar"] - before["columnar"] == shards
                assert after["per_lane"] == before["per_lane"]
                _settle(eng)
        for rs in states:
            assert rs.get(30) is not None
    finally:
        nh.close()


def test_retire_lanes_counts_per_lane_only_for_the_rare_classes():
    """``engine_retire_lanes`` grows by the lanes the rounds processed;
    writes are retired by columns, a ReadIndex completion takes its lane
    through the per-lane handler, and so did the bootstrap config change
    each shard applied when it started."""
    start = _retired()
    nh = _host("rb-retire", 4)
    try:
        eng = nh.kernel_engine
        sessions = {sid: nh.get_noop_session(sid) for sid in range(1, 5)}
        with eng.mu:
            _settle(eng)
            assert _retired()["per_lane"] - start["per_lane"] >= 4
            before, processed, states = _retired(), 0, []
            for i in range(5):
                for sid, s in sessions.items():
                    states.append(nh.propose(s, f"k{i}={sid}".encode(), 30))
                assert eng.step_all()
                processed += eng._lanes_processed
            after = _retired()
            assert processed >= 20
            assert after["columnar"] - before["columnar"] == processed
            assert after["per_lane"] == before["per_lane"]
            _settle(eng)
            before, processed = _retired(), 0
            read = nh.read_index(2, 30)
            for _ in range(20):
                if not eng.step_all():
                    break
                processed += eng._lanes_processed
            after = _retired()
            assert after["per_lane"] - before["per_lane"] == 1
            assert (after["columnar"] - before["columnar"]
                    + after["per_lane"] - before["per_lane"]) == processed
        assert read.get(30) is not None
        for rs in states:
            assert rs.get(30) is not None
    finally:
        nh.close()


def _named() -> dict:
    snap = telemetry.GLOBAL.snapshot()
    return {by: snap.get(f"engine_retire_named{{by={by}}}", 0)
            for by in ("device", "host")}


@pytest.mark.parametrize("depth", [0, 1])
def test_a_round_retires_the_rows_its_program_named_and_no_others(
        depth, monkeypatch):
    """An engine holding 1,024 rows, 8 of them written to: the download's
    ``active`` column names those 8 (one replica a group: no peer's row
    has anything to do), the round retires them and nothing else, and
    ``engine_retire_named{by=device}`` counts them.  The 1,016 rows that
    hold an idle replica cost the pass one cell of one ``tolist`` each: no
    host array is compared with them (the parent's mask was a dozen numpy
    calls over every row held, PERF.md section 6, PR 36)."""
    from dragonboat_tpu.engine import kernel_engine as ke

    retired = []

    class Retiring(ke._Retiring):
        def __init__(self, lanes, nodes, host, cols):
            retired.append(list(lanes))
            super().__init__(lanes, nodes, host, cols)

    nh = _host(f"rb-named{depth}", 1024, depth=depth, capacity=1024)
    try:
        eng = nh.kernel_engine
        assert len(eng.nodes) == eng.capacity == 1024
        for gone in ("_seen_np", "_triple_np", "_lead_np", "_lead_term_np",
                     "_mask_cols", "_occ_np", "_live_rows"):
            assert not hasattr(eng, gone), gone
        busy = list(range(100, 900, 100))
        sessions = [nh.get_noop_session(sid) for sid in busy]
        with eng.mu:
            _settle(eng)
            monkeypatch.setattr(ke, "_Retiring", Retiring)
            for i in range(3):
                states = [nh.propose(s, f"k{i}=v".encode(), 30)
                          for s in sessions]
                del retired[:]
                before, processed = _named(), 0
                assert eng.step_all()
                processed += eng._lanes_processed
                if depth:               # the retire comes a round late
                    eng.step_all()
                    processed += eng._lanes_processed
                want = sorted(eng.by_shard[sid].lane for sid in busy)
                assert [r for r in retired if r] == [want], f"round {i}"
                after = _named()
                assert after["device"] - before["device"] == processed == 8
                assert after["host"] == before["host"]
                _settle(eng)
        for rs in states:
            assert rs.get(30) is not None
    finally:
        nh.close()


@pytest.mark.parametrize("depth", [0, 1])
def test_a_row_with_an_empty_book_is_retired_and_one_placed_at_a_term_too(
        depth, monkeypatch):
    """A replica that joins is placed with NO peer in its book, and the
    kernel answers its leader all the same: the ``active`` column names
    that row (it asks for no occupancy), so the term it is told of is
    saved.  Started again, the row is placed at that term with nothing to
    save, apply or send: no step moves anything, the column reads 0, and
    the host names the row itself for its first pass (``ctx.injected``),
    where the leader edge 0 -> term fires as the parent's did (it compared
    the download with leader cells it had set to 0 at the injection)."""
    from dragonboat_tpu.engine import kernel_engine as ke

    retired = []

    class Retiring(ke._Retiring):
        def __init__(self, lanes, nodes, host, cols):
            retired.extend((g, int(host[g, eng._at["active"]]))
                           for g in lanes)
            super().__init__(lanes, nodes, host, cols)

    def join():
        nh.start_replica({}, True, KVStateMachine, Config(
            shard_id=7, replica_id=2, election_rtt=10, heartbeat_rtt=2,
            device_resident=True))
        return nh.nodes[7]

    def rounds():
        for _ in range(2 + depth):
            eng.step_all()

    nh = _host(f"rb-join{depth}", 1, depth=depth)
    try:
        eng = nh.kernel_engine
        monkeypatch.setattr(ke, "_Retiring", Retiring)
        with eng.mu:
            _settle(eng)
            node = join()
            rounds()
            assert not eng._kind_np[node.lane].any(), "the book is not empty"
            # its leader's heartbeat, from a later term
            nh._handle_message_batch(pb.MessageBatch(
                requests=(pb.Message(
                    type=pb.MessageType.HEARTBEAT, to=2, from_=1,
                    shard_id=7, term=7),),
                source_address=f"rb-join{depth}-9"))
            del retired[:]
            rounds()
            cells = [a for g, a in retired if g == node.lane]
            assert cells and cells[0] & ke.ACTIVE_TRIPLE, retired
            assert (node._leader_cache, node._leader_term_cache) == (1, 7)
            assert nh.logdb.read_raft_state(7, 2, 0).state.term == 7
            # ...and the replica starts again, at the term it saved
            nh.stop_replica(7)
            _settle(eng)
            node = join()
            del retired[:]
            before = _named()
            # (nothing is staged for the row: the round that first carries
            # it is the next one a tick makes)
            assert _wait(lambda: eng.step_all() and any(
                g == node.lane for g, _a in retired), 30), "never retired"
            rounds()
            assert [a for g, a in retired if g == node.lane] == [0], retired
            assert _named()["host"] - before["host"] == 1
            assert (node._leader_cache, node._leader_term_cache) == (0, 7)
    finally:
        nh.close()


def _entry_arrays() -> dict:
    snap = telemetry.GLOBAL.snapshot()
    return {d: snap.get(f"engine_entry_arrays{{dir={d}}}")
            for d in ("in", "out")}


@pytest.mark.parametrize("depth", [0, 1])
def test_entry_takes_and_returns_a_handful_of_arrays(depth):
    """The serial entry's flattened arguments and results, as the gauge
    ``engine_entry_arrays`` reports them from the dispatch's first call:
    the resident state's three arrays and the upload in, the state's three
    and the download out; at most 6 each way."""
    nh = _host(f"rb-arrays{depth}", 1, depth=depth)
    try:
        eng = nh.kernel_engine
        with eng.mu:
            _settle(eng)
            assert eng._dispatch.entry_arrays == (4, 4)
            got = _entry_arrays()
        assert got == {"in": 4, "out": 4}
        assert max(got.values()) <= 6
    finally:
        nh.close()


def test_state_property_is_not_read_inside_a_round(monkeypatch):
    """``engine.state`` unpacks the resident form into a ShardState of 45
    device arrays, for callers outside a round: over 20 busy rounds, the
    fleet / health / invariant rounds among them, ``step_all`` reads it 0
    times.  Outside a round it is the state the rounds left."""
    from dragonboat_tpu.engine.kernel_engine import KernelEngine

    reads = []
    prop = KernelEngine.state
    monkeypatch.setattr(KernelEngine, "state", property(
        lambda self: reads.append(1) or prop.fget(self), prop.fset))
    nh = _host("rb-prop", 4)
    try:
        eng = nh.kernel_engine
        sessions = {sid: nh.get_noop_session(sid) for sid in range(1, 5)}
        with eng.mu:
            _settle(eng)
            reads.clear()
            collections0 = capacity.METER.counts().get("digest_down", 0)
            states = []
            for i in range(20):
                for sid, s in sessions.items():
                    states.append(nh.propose(s, f"p{i}={sid}".encode(), 30))
                assert eng.step_all()
            assert capacity.METER.counts()["digest_down"] \
                == collections0 + 20 // EVERY
            assert reads == [], "step_all read engine.state"
            state = eng.state
            assert reads == [1]
            assert state.lt is eng._resident.lt, "the resident ring itself"
            assert int(state.committed[eng.by_shard[1].lane]) >= 20
        for rs in states:
            assert rs.get(30) is not None
    finally:
        nh.close()


def test_write_cells_and_the_state_setter():
    """The small writers of the resident state: ``_write_cells`` sets a
    [G] or [G, P] field of given rows by one program and one upload (what
    a lane's clearing and a membership's peer-book write run), padded to
    its size class; ``engine.state = s`` packs ``s`` and the getter gives
    it back field for field."""
    import jax
    import numpy as np

    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.engine import kernel_engine as ke

    kp = KP.KernelParams()
    eng = ke.KernelEngine(kp, capacity=6, send_message=None)
    try:
        before = jax.tree.map(np.asarray, eng.state)
        m0 = capacity.METER.counts().get("membership_up", 0)
        pids = np.arange(1, kp.num_peers + 1, dtype=np.int32)
        eng._write_cells(((2, "pid", pids), (2, "kind", KP.K_VOTER),
                          (4, "pid", pids[::-1]), (2, "pending_cc", True),
                          (5, "term", 7)), "membership_up")
        assert capacity.METER.counts()["membership_up"] == m0 + 1
        got = jax.tree.map(np.asarray, eng.state)
        want = before._replace(
            pid=before.pid.copy(), kind=before.kind.copy(),
            pending_cc=before.pending_cc.copy(), term=before.term.copy())
        want.pid[2], want.pid[4], want.kind[2] = pids, pids[::-1], KP.K_VOTER
        want.pending_cc[2], want.term[5] = True, 7
        for f in want._fields:
            a, b = getattr(got, f), getattr(want, f)
            assert (a is None and b is None) or (
                a.dtype == b.dtype and np.array_equal(a, b)), f
        # a lane's clearing, through the same program
        eng._clear_lane(2)
        cleared = eng.state
        assert not np.asarray(cleared.pid[2]).any()
        assert (np.asarray(cleared.kind[2]) == KP.K_ABSENT).all()
        assert np.array_equal(np.asarray(cleared.pid[4]), pids[::-1])
        # the setter packs what the getter unpacks
        eng.state = want
        again = jax.tree.map(np.asarray, eng.state)
        assert all(np.array_equal(getattr(again, f), getattr(want, f))
                   for f in want._fields if getattr(want, f) is not None)
        assert len(jax.tree.leaves(eng._resident)) == 3
    finally:
        eng.close()


def test_client_calls_do_not_wait_for_the_host_lock():
    """Every client call looks its node up first (``NodeHost._node``), and
    so does every inbound message: one dict read, without the host's lock.  A thread that found that lock held
    gave the interpreter up and then waited a switch interval or more to
    get it back from an engine in the middle of a round; since a round no
    longer hands the interpreter over 46 times, that wait was most of what
    a client thread did (PERF.md section 6, PR 28)."""
    nh = _host("rb-lock", 1)
    try:
        s = nh.get_noop_session(1)
        nh.sync_propose(s, b"k=v", timeout_s=30)
        held, release = threading.Event(), threading.Event()

        def hold():
            with nh.mu:
                held.set()
                release.wait(30)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        assert held.wait(10)
        try:
            t0 = time.perf_counter()
            assert nh.stale_read(1, "k") == "v"
            assert nh.get_leader_id(1) == (1, True)
            rs = nh.propose(s, b"k2=v2", 30)
            # the inbound message path too: on the loopback transport it
            # runs on the sending engine's thread, inside its round
            nh._handle_message_batch(pb.MessageBatch(
                requests=(pb.Message(type=pb.MessageType.HEARTBEAT_RESP,
                                     shard_id=1, to=1, from_=2, term=1),),
                deployment_id=nh.config.deployment_id))
            took = time.perf_counter() - t0
        finally:
            release.set()
            t.join(10)
        assert not t.is_alive()
        assert took < 5, "a client call waited for the host lock"
        assert rs.get(30) is not None
    finally:
        nh.close()


def _spin(stop):
    x = 0
    while not stop.is_set():
        x += 1


def test_round_call_beside_three_busy_threads():
    """The mechanism, on the CPU: a round's ``_kernel_call`` and the
    rebinding of the state beside three spinning Python threads.  Every
    device array a round lets go of is a wait for the interpreter; with a
    ShardState's 45 a call took 120-930 ms here, with the resident three
    1-100, a handful of waits of 0-20 ms each (PERF.md section 6, PR 28).
    The median of 20 is held under 100 ms: a single call still meets an
    unlucky run of waits, more so beside other test workers."""
    nh = _host("rb-spin", 1)
    stop = threading.Event()
    spinners = [threading.Thread(target=_spin, args=(stop,), daemon=True)
                for _ in range(3)]
    try:
        eng = nh.kernel_engine
        with eng.mu:
            _settle(eng)
            staging = eng._bufs[eng._buf_idx][0]
            staging.reset()
            for t in spinners:
                t.start()
            time.sleep(0.1)
            took = []
            for _ in range(20):
                t0 = time.perf_counter()
                resident, down = eng._kernel_call(staging)
                eng._resident = resident
                took.append((time.perf_counter() - t0) * 1e3)
                del resident, down
        assert sorted(took)[len(took) // 2] < 100, \
            [round(t, 1) for t in took]
    finally:
        stop.set()
        for t in spinners:
            t.join(10)
        nh.close()
    assert not any(t.is_alive() for t in spinners)


def test_nothing_compiles_while_saved_rows_vary_1_to_48():
    """Rounds that save 1, 2, ... 48 lanes' entries: the download is one
    fixed shape, so after the first round no tracked entry compiles and
    XLA compiles nothing at all (the per-count ``state.lt[idx]`` gather
    compiled ~7 small programs for every new count), and no lane leaves
    the download's save window."""
    nh = _host("rb-compile", SHARDS)
    try:
        eng = nh.kernel_engine
        sessions = {sid: nh.get_noop_session(sid)
                    for sid in range(1, SHARDS + 1)}
        overflow0 = telemetry.GLOBAL.snapshot().get(
            "engine_save_window_overflow", 0)
        with eng.mu:
            _settle(eng)
            # the first round, and one set of the every-tenth-round
            # collections, may compile (the cluster's start already did)
            rs = nh.propose(sessions[1], b"warm=1", 30)
            for _ in range(EVERY):
                eng.step_all()
            tracked0, xla0 = _tracked_compiles(eng), _xla_compiles()
            states = [rs]
            for k in range(1, SHARDS + 1):
                for sid in range(1, k + 1):
                    states.append(nh.propose(
                        sessions[sid], f"r{k}={sid}".encode(), 30))
                calls0 = eng._cap_entries["step"].stats()["calls"]
                assert eng.step_all()
                assert eng._cap_entries["step"].stats()["calls"] == calls0 + 1
                saved = sum(1 for sid in range(1, k + 1)
                            if states[-sid].get(30) is not None)
                assert saved == k, f"round {k} acknowledged {saved} writes"
            assert _tracked_compiles(eng) == tracked0
            assert _xla_compiles() == xla0, "a round compiled something"
        assert telemetry.GLOBAL.snapshot().get(
            "engine_save_window_overflow", 0) == overflow0
        for sid in (1, SHARDS):
            assert nh.sync_read(sid, f"r{SHARDS}", 30) == str(sid)
    finally:
        nh.close()


def _persisted(nh, sid=1):
    rs = nh.logdb.read_raft_state(sid, 1, 0)
    ents = nh.logdb.iterate_entries(
        sid, 1, rs.first_index, rs.first_index + rs.entry_count, 0)
    return [(e.index, e.term, bytes(e.cmd)) for e in ents
            if not e.is_config_change()]


def test_lane_past_the_save_window_takes_its_ring_row(monkeypatch):
    """A kernel parameter set whose download carries only 2 ring entries
    per lane: a step that appends 8 leaves the window, the engine reads
    that lane's whole ring row once (fixed shape) and counts it, and the
    entries it persists are those the host-resident pycore node persists
    for the same proposals."""
    cmds = [f"w{i}={i}".encode() for i in range(8)]

    oracle = _host("rb-oracle", 1, device=False)
    try:
        s = oracle.get_noop_session(1)
        for rs in [oracle.propose(s, c, 30) for c in cmds]:
            rs.get(30)
        want = _persisted(oracle)
    finally:
        oracle.close()

    picked = NodeHost._kernel_params

    def small_window(self, min_inbox: int = 0):
        return dataclasses.replace(picked(self, min_inbox), save_window=2)

    monkeypatch.setattr(NodeHost, "_kernel_params", small_window)
    counter = "engine_save_window_overflow"
    before = telemetry.GLOBAL.snapshot().get(counter, 0)
    nh = _host("rb-small", 1)
    try:
        eng = nh.kernel_engine
        assert eng.kp.save_window == 2
        s = nh.get_noop_session(1)
        with eng.mu:
            _settle(eng)
            m0 = capacity.METER.counts()
            states = [nh.propose(s, c, 30) for c in cmds]
            assert eng.step_all()
            m1 = capacity.METER.counts()
        for rs in states:
            rs.get(30)
        assert m1.get("save_window_row", 0) - m0.get("save_window_row", 0) \
            == 1
        assert telemetry.GLOBAL.snapshot()[counter] == before + 1
        got = _persisted(nh)
    finally:
        nh.close()
    assert [c for _i, _t, c in got][-8:] == cmds
    assert got == want


def test_tick_no_faster_than_the_engines_recent_rounds():
    """The logical clock: one tick a step, but no sooner after the last
    than the engine's recent rounds took (capped): an engine between
    bursts of work must not run its election and heartbeat clocks several
    times faster than a loaded peer's."""
    from dragonboat_tpu.engine import kernel_engine as ke

    nh = _host("rb-tick", 1)
    try:
        eng = nh.kernel_engine
        with eng.mu:
            _settle(eng)
            # the floor follows the rounds' length: up by at most a
            # doubling a round (one 10 s compile round moves it 5 ms), down
            # a sixteenth a round, never above the cap
            eng._tick_floor_us = 0
            eng._round_t0_us = ke.monotonic_us() - 10_000_000
            eng._commit_round(0, ())
            assert eng._tick_floor_us == 5_000
            for _ in range(8):
                eng._round_t0_us = ke.monotonic_us() - 40_000
                eng._commit_round(0, ())
            assert 40_000 <= eng._tick_floor_us < 45_000
            for _ in range(8):
                eng._round_t0_us = ke.monotonic_us() - 10_000_000
                eng._commit_round(0, ())
            assert eng._tick_floor_us == ke._MAX_TICK_FLOOR_US
            eng._round_t0_us = ke.monotonic_us()
            eng._commit_round(0, ())
            assert eng._tick_floor_us == ke._MAX_TICK_FLOOR_US * 15 // 16

            def ticked():
                before = capacity.METER.counts().get("round_up", 0)
                eng.tick_round()
                eng.step_all()
                return capacity.METER.counts().get("round_up", 0) - before

            # a tick is pending, but the last one is 1 ms old: no round
            eng._tick_floor_us = 50_000
            eng._last_tick_us = ke.monotonic_us() - 1_000
            assert ticked() == 0
            # ...and once the floor has passed, the pending tick is a round
            eng._last_tick_us = ke.monotonic_us() - 60_000
            assert ticked() == 1
    finally:
        nh.close()


def test_a_later_heartbeat_supersedes_the_queued_ones():
    """Staging keeps, of the heartbeats queued from one sender in one
    term, only the last (its commit is monotone and its ReadIndex ctx the
    newest pending one): a lane that fell behind answers the newest, not
    a backlog.  Everything else keeps its place and order."""
    from dragonboat_tpu import raftpb as pb
    from dragonboat_tpu.engine.kernel_engine import _newest_heartbeats

    MT = pb.MessageType

    def hb(frm, term, commit, hint=0):
        return pb.Message(type=MT.HEARTBEAT, from_=frm, to=2, shard_id=1,
                          term=term, commit=commit, hint=hint)

    rep = pb.Message(type=MT.REPLICATE, from_=1, to=2, shard_id=1, term=3)
    resp = pb.Message(type=MT.HEARTBEAT_RESP, from_=3, to=2, shard_id=1,
                      term=3)
    a, b, c = hb(1, 3, 10), hb(1, 3, 11, hint=7), hb(1, 3, 12, hint=9)
    old_term, other = hb(1, 2, 5), hb(3, 3, 4)
    msgs = [old_term, a, rep, b, other, resp, c]
    assert _newest_heartbeats(msgs) == [old_term, rep, other, resp, c]
    single = [a, rep, other]
    assert _newest_heartbeats(single) is single      # nothing to drop


def test_forwarded_read_the_leader_turned_away_waits_its_turn():
    """A follower host's forwarded ReadIndex that the leader's kernel drops
    (its ReadIndex book full) is staged again next round, ahead of later
    ones: the requester would otherwise hear nothing until its timeout.
    Once the shard is no longer led from here it is let go."""
    from types import SimpleNamespace

    import numpy as np

    from dragonboat_tpu import raftpb as pb
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.engine import kernel_engine as ke

    eng = ke.KernelEngine(KP.KernelParams(), capacity=4, send_message=None)
    ctx, later = pb.SystemCtx(low=7, high=1), pb.SystemCtx(low=9, high=1)
    o = {"ri_dropped": np.array([False, True, False, False])}
    flags = np.zeros((len(ke.FLAG_CLASSES),), bool)

    def node(leader: bool):
        return SimpleNamespace(
            _local_ri_pending={}, _remote_ri_inflight={7: 3},
            _remote_reads=[(2, later, 0)], is_leader=lambda: leader,
            pending_reads=SimpleNamespace(applied=lambda i: None),
            sm=SimpleNamespace(get_last_applied=lambda: 0))

    n = node(leader=True)
    eng._complete_reads(1, n, o, flags, ctx)
    assert not n._remote_ri_inflight
    assert [(s, c) for s, c, _t in n._remote_reads] == [(3, ctx), (2, later)]
    n = node(leader=False)
    eng._complete_reads(1, n, o, flags, ctx)
    assert not n._remote_ri_inflight
    assert [(s, c) for s, c, _t in n._remote_reads] == [(2, later)]
    eng.close()

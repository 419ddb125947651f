"""Fault-injection seams, unit-level: CrashPointFS (crash-at-Nth-op,
torn writes), the breaker's capped exponential backoff, the ErrorFS
fail_after matrix over every op against the snapshot writer and the env
flag files, per-subtree MemFS power loss, tan log quarantine, and the
controlled-crash -> restart() acceptance paths.

The composed end-to-end schedules live in test_chaos_schedules.py; this
file proves each seam in isolation so a schedule failure bisects.
"""

import json
import os
import time

import pytest

from dragonboat_tpu.chaos import CrashPointFS, FaultPlan
from dragonboat_tpu.chaos.faultplan import DOWN_KINDS, HEAL_FOR
from dragonboat_tpu.chaos.oracle import (
    check_monotone_applied,
    check_no_acked_loss,
    check_prefix_consistent,
)
from dragonboat_tpu.chaos.runner import _Cluster
from dragonboat_tpu.logdb.tan import CorruptLogError, TanLogDB
from dragonboat_tpu.transport.hub import (
    BREAKER_JITTER,
    CircuitBreaker,
)
from dragonboat_tpu.vfs import ErrorFS, InjectedError, MemFS
from dragonboat_tpu import raftpb as pb


# -- CrashPointFS ------------------------------------------------------------


def test_crashfs_counts_down_then_sticks():
    fs = CrashPointFS(MemFS())
    fs.arm(after_ops=2)
    with fs.open("/f", "wb") as f:
        f.write(b"a")            # matching op 1
        f.write(b"b")            # matching op 2
        with pytest.raises(InjectedError):
            f.write(b"c")        # trips
        assert fs.tripped
        with pytest.raises(InjectedError):
            f.write(b"d")        # stays dead until healed
    assert fs.trip_count == 2
    fs.heal()
    with fs.open("/f", "ab") as f:
        f.write(b"e")
    with fs.open("/f", "rb") as f:
        assert f.read() == b"abe"


def test_crashfs_fsync_is_a_matching_op():
    fs = CrashPointFS(MemFS())
    fs.arm(after_ops=1)
    with fs.open("/f", "wb") as f:
        f.write(b"a")                    # op 1
        with pytest.raises(InjectedError):
            fs.fsync(f)                  # op 2 trips


def test_crashfs_torn_write_lands_a_prefix():
    mem = MemFS()
    fs = CrashPointFS(mem)
    fs.arm(after_ops=1, torn=True)
    with fs.open("/f", "wb") as f:
        f.write(b"12345678")
        with pytest.raises(InjectedError):
            f.write(b"ABCDEFGH")         # torn: a strict prefix lands
    with mem.open("/f", "rb") as f:
        data = f.read()
    assert data.startswith(b"12345678")
    tail = data[8:]
    assert 0 < len(tail) < 8 and b"ABCDEFGH".startswith(tail)
    # only the TRIPPING write tears; the stuck state fails cleanly
    fs2 = CrashPointFS(mem)
    fs2.arm(after_ops=0, torn=True)
    with pytest.raises(InjectedError):
        with fs2.open("/g", "wb") as f:
            f.write(b"XY")
    with mem.open("/g", "rb") as f:
        assert f.read() == b"X"


def test_crashfs_path_substr_scopes_the_fault():
    fs = CrashPointFS(MemFS(), path_substr="/wal/")
    fs.arm(after_ops=0)
    with fs.open("/data/f", "wb") as f:
        f.write(b"fine")                 # not under /wal/
    with fs.open("/wal/g", "wb") as f:
        with pytest.raises(InjectedError):
            f.write(b"boom")


def test_crashfs_unarmed_is_transparent():
    fs = CrashPointFS(MemFS())
    with fs.open("/f", "wb") as f:
        f.write(b"data")
        fs.fsync(f)
    assert not fs.tripped and fs.trip_count == 0


# -- MemFS.crash(prefix): per-host power loss on a shared tree ---------------


def test_memfs_crash_prefix_scopes_power_loss():
    mem = MemFS()
    for host in ("/a", "/b"):
        with mem.open(host + "/synced", "wb") as f:
            f.write(b"durable")
            mem.fsync(f)
        with mem.open(host + "/dirty", "wb") as f:
            f.write(b"volatile")
    mem.crash("/a")
    assert not mem.exists("/a/dirty")          # unsynced: gone
    with mem.open("/a/synced", "rb") as f:
        assert f.read() == b"durable"
    with mem.open("/b/dirty", "rb") as f:      # other subtree untouched
        assert f.read() == b"volatile"


# -- CircuitBreaker backoff --------------------------------------------------


def test_breaker_closed_open_halfopen_closed():
    b = CircuitBreaker(reset_after=1.0, max_reset=30.0, seed=0)
    assert b.state(now=0.0) == "closed"
    assert b.ready(now=5.0)      # fresh breaker: ready once base elapses
    b.fail(now=10.0)
    assert b.state(now=10.0) == "open"
    assert not b.ready(now=10.5)
    # first cooldown: base * (1 + jitter in [0, BREAKER_JITTER))
    assert 1.0 <= b.reset_after <= 1.0 * (1 + BREAKER_JITTER)
    t = 10.0 + b.reset_after
    assert b.state(now=t) == "half-open"
    assert b.ready(now=t)
    b.succeed()
    assert b.state(now=t) == "closed"
    assert b.reset_after == 1.0          # backoff fully reset


def test_breaker_backoff_doubles_and_caps():
    b = CircuitBreaker(reset_after=1.0, max_reset=30.0, seed=3)
    seen = []
    for i in range(8):
        b.fail(now=float(i * 1000))
        seen.append(b.reset_after)
    # 2x growth dominates the <=25% jitter: strictly increasing to the cap
    for prev, cur in zip(seen, seen[1:]):
        assert cur > prev or cur == 30.0
    assert seen[-1] == 30.0
    assert not b.ready(now=7000.0 + 29.9)
    assert b.ready(now=7000.0 + 30.0)
    b.succeed()
    b.fail(now=99999.0)
    assert b.reset_after <= 1.0 * (1 + BREAKER_JITTER)


def test_breaker_jitter_is_seed_deterministic():
    fails = [float(i * 100) for i in range(6)]

    def cooldowns(seed):
        b = CircuitBreaker(reset_after=1.0, max_reset=3600.0, seed=seed)
        out = []
        for t in fails:
            b.fail(now=t)
            out.append(b.reset_after)
        return out

    assert cooldowns(7) == cooldowns(7)          # replayable
    assert cooldowns(7) != cooldowns(8)          # but per-seed distinct


def test_hub_trip_breaker_forces_open():
    from dragonboat_tpu.chaos.runner import ChaosKV
    from dragonboat_tpu.config import Config, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost

    nh = NodeHost(NodeHostConfig(raft_address="trip-1", rtt_millisecond=5))
    try:
        nh.start_replica({1: "trip-1"}, False, ChaosKV,
                         Config(shard_id=1, replica_id=1, election_rtt=10,
                                heartbeat_rtt=1))
        b = nh.hub.trip_breaker("elsewhere-1", count=3)
        assert b.state() == "open"
        assert b.trip_streak == 3
        # same addr on a fresh hub -> same per-addr jitter seed: the
        # cooldown sequence is identical (replay contract)
        b2 = CircuitBreaker(seed=__import__("zlib").crc32(b"elsewhere-1"))
        for _ in range(3):
            b2.fail(now=0.0)
        assert b2.reset_after == b.reset_after
    finally:
        nh.close()


# -- ErrorFS fail_after matrix: snapshot writer + env flag files -------------

_ALL_OPS = ("open", "write", "read", "fsync", "remove", "replace", "listdir")


def _snapshot_env_workload(fs, root="/d"):
    """Exercises every ErrorFS op against the two durability surfaces the
    matrix targets: rsm/snapshotio.py container IO and server/env.py
    flag files."""
    from dragonboat_tpu.rsm.snapshotio import read_snapshot, write_snapshot
    from dragonboat_tpu.server.env import Env

    env = Env(root, "addr-1", fs=fs)
    env.check_node_host_dir("sharded-tan")       # flag: open/write/fsync/replace
    snap = os.path.join(env.root, "snap.gbsnap")
    tmp = snap + ".tmp"
    with fs.open(tmp, "wb") as f:
        write_snapshot(f, b"sess", lambda w: w.write(b"payload" * 64))
        fs.fsync(f)
    fs.replace(tmp, snap)
    with fs.open(snap, "rb") as f:
        sess, reader = read_snapshot(f)
        assert sess == b"sess"
        assert reader.read() == b"payload" * 64
    assert "snap.gbsnap" in fs.listdir(env.root)
    scratch = os.path.join(env.root, "scratch")
    with fs.open(scratch, "wb") as f:
        f.write(b"x")
    fs.remove(scratch)
    env.close()


def _op_counts():
    counts = {}

    def tally(op, path):
        counts[op] = counts.get(op, 0) + 1
        return False

    _snapshot_env_workload(ErrorFS(MemFS(), tally))
    return counts


@pytest.mark.parametrize("op", _ALL_OPS)
def test_fail_after_matrix_controlled_crash_then_recover(op):
    """For every ErrorFS op: fail it early / midway / last, assert the
    workload dies with InjectedError (controlled crash, never silent
    corruption), then heal and assert full recovery — with the flag file
    either absent or complete valid JSON at every crash point (the
    tmp+fsync+replace discipline of env._write_flag)."""
    n = _op_counts()[op]
    assert n >= 1, f"workload never performs {op!r}"
    for after in sorted({0, n // 2, n - 1}):
        mem = MemFS()
        fs = CrashPointFS(mem, ops=(op,))
        fs.arm(after_ops=after)
        with pytest.raises(InjectedError):
            _snapshot_env_workload(fs)
        assert fs.tripped
        # atomicity at the crash point: a flag file, if present, parses
        flag = "/d/addr-1/dragonboat.ds"
        if mem.exists(flag):
            with mem.open(flag, "r") as f:
                assert json.loads(f.read())["address"] == "addr-1"
        fs.heal()
        _snapshot_env_workload(fs)       # recovery: the same dir reopens


# -- tan quarantine: corrupt NON-TAIL record ---------------------------------


def _fill_tan(root, fs, n_entries=60, max_file_size=512):
    db = TanLogDB(root, max_file_size=max_file_size, fs=fs)
    for i in range(1, n_entries + 1):
        db.save_raft_state([pb.Update(
            shard_id=1, replica_id=1,
            state=pb.State(term=1, vote=1, commit=i),
            entries_to_save=(pb.Entry(index=i, term=1,
                                      cmd=f"cmd-{i:04d}".encode()),),
        )], worker_id=0)
    db.close()


def _tan_files(root, fs):
    return sorted(f for f in fs.listdir(root) if f.endswith(".tan"))


def test_tan_corrupt_nontail_strict_refuses_quarantine_recovers():
    mem = MemFS()
    _fill_tan("/tan", mem)
    files = _tan_files("/tan", mem)
    assert len(files) >= 3, "need multiple files to corrupt a non-tail one"
    victim = os.path.join("/tan", files[len(files) // 2])
    with mem.open(victim, "r+b") as f:
        size = len(f.read())
        f.seek(size // 2)
        f.write(b"\xff")                 # flip mid-file: non-tail corruption
    with pytest.raises(CorruptLogError):
        TanLogDB("/tan", max_file_size=512, fs=mem)
    db = TanLogDB("/tan", max_file_size=512, fs=mem,
                  recovery_mode="quarantine")
    try:
        assert db.quarantined and victim in db.quarantined[0]
        rs = db.read_raft_state(1, 1, 0)
        # the commit clamp: persisted commit (60) exceeded what survived,
        # so it was pulled back inside the contiguous range still on disk
        assert rs is not None
        avail = rs.first_index + rs.entry_count - 1
        assert 0 < rs.state.commit <= avail < 60
        # the surviving prefix reads back intact
        ents = db.iterate_entries(1, 1, rs.first_index, avail + 1, 0)
        assert [e.cmd for e in ents] == [
            f"cmd-{i:04d}".encode()
            for i in range(rs.first_index, avail + 1)]
    finally:
        db.close()


def test_tan_tail_file_torn_truncation_still_default():
    mem = MemFS()
    _fill_tan("/tan", mem, n_entries=20, max_file_size=1 << 20)
    files = _tan_files("/tan", mem)
    assert len(files) == 1
    victim = os.path.join("/tan", files[0])
    with mem.open(victim, "r+b") as f:
        size = len(f.read())
        f.seek(size - 3)
        f.write(b"\xff")                 # torn tail: strict mode truncates
    db = TanLogDB("/tan", max_file_size=1 << 20, fs=mem)   # strict: opens
    try:
        assert db.quarantined == []
        rs = db.read_raft_state(1, 1, 0)
        assert rs is not None and rs.entry_count >= 1
    finally:
        db.close()


# -- acceptance: controlled storage crash -> restart() -> converged ----------


def test_storage_crash_restart_rejoins_converged():
    """ISSUE acceptance: a NodeHost whose CrashPointFS tripped mid-write
    controlled-crashes (fatal_error set, workers parked), then
    restart() reopens the SAME data dir in place and the replica rejoins
    and reconverges — proven by the monkey hash oracles."""
    c = _Cluster(seed=901, n=3)
    try:
        c.start()
        assert c.propose(b"seed=1", timeout=10.0)
        victim = 2
        c.fss[victim].arm(after_ops=3, torn=True)
        assert c._pump_until(
            lambda: c.hosts[victim].fatal_error is not None, timeout=15.0)
        assert c.hosts[victim]._stopped          # controlled crash, not hung
        assert c.live_rids() == [1, 3]
        assert c.propose(b"during=crash", timeout=10.0)   # quorum holds
        c.fss[victim].heal()
        c.hosts[victim].restart()
        c.epochs[victim] += 1
        c.reset_breakers()
        assert c.propose(b"after=restart", timeout=10.0)
        deadline = time.time() + 20
        while time.time() < deadline:
            js = c.journals()
            if len(js) == 3 and len({tuple(j) for j in js.values()}) == 1:
                break
            time.sleep(0.1)
        js = c.journals()
        assert len(js) == 3
        assert check_prefix_consistent(js).ok
        assert len({tuple(j) for j in js.values()}) == 1, {
            r: len(j) for r, j in js.items()}
        for kind in ("sm", "session", "membership"):
            hs = c.hashes(kind)
            assert len(set(hs.values())) == 1, (kind, hs)
    finally:
        c.close()


def test_restart_refuses_live_host():
    from dragonboat_tpu.chaos.runner import ChaosKV
    from dragonboat_tpu.config import Config, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.request import RequestError

    nh = NodeHost(NodeHostConfig(raft_address="live-1", rtt_millisecond=5))
    try:
        nh.start_replica({1: "live-1"}, False, ChaosKV,
                         Config(shard_id=1, replica_id=1, election_rtt=10,
                                heartbeat_rtt=1))
        with pytest.raises(RequestError):
            nh.restart()                 # only a stopped host restarts
    finally:
        nh.close()


# -- acceptance: corrupt non-tail log on disk -> snapshot re-replication -----


def test_corrupt_follower_log_requarantines_and_rejoins(tmp_path):
    """A follower's tan log corrupted mid-history (non-tail) under
    recovery_mode="quarantine" reopens, clamps, and is re-replicated
    back to the shard state — via leader snapshot when the lost suffix
    is already compacted away.  Real disk: the snapshot path checks
    os.path filepaths."""
    from dragonboat_tpu.chaos.runner import ChaosKV
    from dragonboat_tpu.config import Config, NodeHostConfig
    from dragonboat_tpu.logdb.sharded import ShardedLogDBFactory
    from dragonboat_tpu.nodehost import NodeHost

    from test_nodehost import propose_to_leader

    addrs = {i: f"cq-{i}" for i in (1, 2, 3)}

    def mk(rid, mode="quarantine"):
        nh = NodeHost(NodeHostConfig(
            raft_address=addrs[rid], rtt_millisecond=5,
            node_host_dir=str(tmp_path),
            logdb_factory=ShardedLogDBFactory(
                str(tmp_path / f"db-{rid}"), num_shards=1,
                max_file_size=1024, recovery_mode=mode)))
        nh.start_replica(dict(addrs), False, ChaosKV, Config(
            shard_id=1, replica_id=rid, election_rtt=10, heartbeat_rtt=1,
            snapshot_entries=10, compaction_overhead=3))
        return nh

    def leader_of(hosts, timeout=10.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            for rid in sorted(hosts):
                lid, ok = hosts[rid].get_leader_id(1)
                if ok and lid in hosts:
                    return lid
            time.sleep(0.05)
        raise AssertionError("no leader elected")

    hosts = {rid: mk(rid) for rid in addrs}
    try:
        for i in range(60):
            propose_to_leader(hosts, f"k{i}=v{i}".encode())
        leader = leader_of(hosts)
        victim = next(r for r in (1, 2, 3) if r != leader)
        # wait for the victim to have applied everything, then detach it
        deadline = time.time() + 10
        while time.time() < deadline and \
                hosts[victim].stale_read(1, "k59") != "v59":
            time.sleep(0.05)
        assert hosts[victim].stale_read(1, "k59") == "v59"
        hosts[victim].close()

        part = tmp_path / f"db-{victim}" / "part-00"
        tans = sorted(p for p in os.listdir(part) if p.endswith(".tan"))
        assert len(tans) >= 3
        vf = part / tans[len(tans) // 2]
        blob = bytearray(vf.read_bytes())
        blob[len(blob) // 2] ^= 0xFF     # corrupt a non-tail record
        vf.write_bytes(bytes(blob))

        # strict mode refuses the directory outright
        with pytest.raises(CorruptLogError):
            NodeHost(NodeHostConfig(
                raft_address=addrs[victim], rtt_millisecond=5,
                node_host_dir=str(tmp_path),
                logdb_factory=ShardedLogDBFactory(
                    str(tmp_path / f"db-{victim}"), num_shards=1,
                    max_file_size=1024, recovery_mode="strict")))

        # quarantine mode reopens and the shard heals the replica
        hosts[victim] = mk(victim)
        assert hosts[victim].logdb.quarantined
        # keep the shard moving so compaction passes the lost range
        for i in range(60, 75):
            propose_to_leader(hosts, f"k{i}=v{i}".encode())
        deadline = time.time() + 30
        ok = False
        while time.time() < deadline and not ok:
            ok = all(hosts[victim].stale_read(1, f"k{i}") == f"v{i}"
                     for i in range(75))
            time.sleep(0.1)
        assert ok, "quarantined replica never reconverged"
        hs = {r: h.get_sm_hash(1) for r, h in hosts.items()}
        deadline = time.time() + 10
        while time.time() < deadline and len(set(hs.values())) != 1:
            time.sleep(0.1)
            hs = {r: h.get_sm_hash(1) for r, h in hosts.items()}
        assert len(set(hs.values())) == 1, hs
    finally:
        for h in hosts.values():
            try:
                h.close()
            except Exception:
                pass


# -- FaultPlan generator invariants ------------------------------------------


def test_faultplan_same_seed_same_bytes():
    for seed in range(30):
        a = FaultPlan.generate(seed).to_json()
        b = FaultPlan.generate(seed).to_json()
        assert a == b
        assert FaultPlan.from_json(a).to_json() == a


def test_faultplan_invariants_over_many_seeds():
    """Every generated schedule is recoverable by construction: at most
    one replica down at a time, every fault healed by the end, final
    step all-clear."""
    for seed in range(60):
        plan = FaultPlan.generate(seed)
        down = None
        open_soft = set()
        for ev in plan.events:
            if ev.kind in DOWN_KINDS:
                assert down is None, (seed, ev)
                down = (ev.target, ev.kind)
            elif ev.kind in ("restart_inplace", "restart_process",
                             "restore_partition"):
                assert down is not None and down[0] == ev.target \
                    and HEAL_FOR[down[1]] == ev.kind, (seed, ev)
                down = None
            elif ev.kind in ("drop", "delay", "duplicate", "reorder"):
                open_soft.add((ev.target, ev.kind))
            elif ev.kind == "heal_transport":
                open_soft = {(r, k) for r, k in open_soft
                             if r != ev.target}
        assert down is None, seed
        assert not open_soft, (seed, open_soft)


# -- oracle unit checks -------------------------------------------------------


def test_oracle_flags_divergence_and_loss():
    ok = check_prefix_consistent({1: [b"a", b"b"], 2: [b"a"]})
    assert ok.ok
    bad = check_prefix_consistent({1: [b"a", b"b"], 2: [b"a", b"X"]})
    assert not bad.ok and "diverge" in bad.failures[0]
    lost = check_no_acked_loss([b"a", b"z"], {1: [b"a"]})
    assert not lost.ok and "lost" in lost.failures[0]


def test_oracle_monotone_applied_respects_restart_epochs():
    # regression within one epoch: flagged
    bad = check_monotone_applied({1: [(0, 5), (0, 3)]})
    assert not bad.ok
    # a restart (epoch bump) legitimately replays from a lower index
    good = check_monotone_applied({1: [(0, 5), (1, 2), (1, 9)]})
    assert good.ok

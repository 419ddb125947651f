"""Sharded tan engine: partition routing, restart recovery across
partitions, overlapping fsyncs (the single-lock bug the r3 VERDICT
flagged), geometry pinning, legacy-layout migration, and spanning-batch
saves from the device engine's [G]-batch shape.

Parity target: internal/logdb/sharded.go:34-80 (ShardedDB over N
single-writer DBs), internal/server/partition.go:59 (DoubleFixed
partitioner), raftio/logdb.go:78-83 (single-writer-per-worker fsync
contract)."""

import os
import threading
import time

import pytest

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.logdb.sharded import (
    ShardedLogDB,
    ShardGeometryError,
)
from dragonboat_tpu.logdb.tan import TanLogDB


def _update(shard=1, replica=1, term=1, first=1, n=3, commit=0):
    ents = tuple(
        pb.Entry(term=term, index=first + i, cmd=f"s{shard}e{first + i}".encode())
        for i in range(n)
    )
    return pb.Update(
        shard_id=shard, replica_id=replica,
        state=pb.State(term=term, vote=2, commit=commit),
        entries_to_save=ents,
    )


def test_routing_spreads_partitions(tmp_path):
    db = ShardedLogDB(str(tmp_path), num_shards=4)
    for sid in range(1, 9):
        db.save_raft_state([_update(shard=sid, n=2)], worker_id=sid % 4)
    # every shard readable through the facade
    for sid in range(1, 9):
        ents = db.iterate_entries(sid, 1, 1, 3, 0)
        assert [e.index for e in ents] == [1, 2]
        assert ents[0].cmd == f"s{sid}e1".encode()
    # and the files really are spread over >1 partition dir
    parts_with_data = [
        d for d in os.listdir(tmp_path)
        if d.startswith("part-")
        and any(f.startswith("log-") and os.path.getsize(
            os.path.join(tmp_path, d, f)) > 0
            for f in os.listdir(os.path.join(tmp_path, d)))
    ]
    assert len(parts_with_data) == 4
    db.close()


def test_restart_across_partitions(tmp_path):
    db = ShardedLogDB(str(tmp_path), num_shards=4)
    db.save_bootstrap_info(3, 1, pb.Bootstrap(addresses={1: "a"}))
    for sid in (1, 2, 3, 6, 7):
        db.save_raft_state([_update(shard=sid, n=4, commit=2)], worker_id=0)
    db.close()

    db2 = ShardedLogDB(str(tmp_path), num_shards=4)
    infos = {(ni.shard_id, ni.replica_id) for ni in db2.list_node_info()}
    assert infos == {(1, 1), (2, 1), (3, 1), (6, 1), (7, 1)}
    for sid in (1, 2, 3, 6, 7):
        rs = db2.read_raft_state(sid, 1, 0)
        assert rs.entry_count == 4 and rs.state.commit == 2
    assert db2.get_bootstrap_info(3, 1).addresses == {1: "a"}
    db2.close()


def test_geometry_change_refused(tmp_path):
    db = ShardedLogDB(str(tmp_path), num_shards=4)
    db.save_raft_state([_update()], worker_id=0)
    db.close()
    with pytest.raises(ShardGeometryError):
        ShardedLogDB(str(tmp_path), num_shards=8)
    with pytest.raises(ShardGeometryError):
        ShardedLogDB(str(tmp_path), num_shards=2)
    # the original geometry still opens
    db2 = ShardedLogDB(str(tmp_path), num_shards=4)
    assert db2.read_raft_state(1, 1, 0) is not None
    db2.close()


def test_legacy_flat_layout_migrates(tmp_path):
    old = TanLogDB(str(tmp_path))
    old.save_bootstrap_info(1, 1, pb.Bootstrap(addresses={1: "x", 2: "y"}))
    for sid in (1, 2, 5):
        old.save_raft_state([_update(shard=sid, n=3, commit=1)], worker_id=0)
    old.save_snapshots([pb.Update(
        shard_id=2, replica_id=1,
        snapshot=pb.Snapshot(index=1, term=1, shard_id=2),
    )])
    old.close()
    assert any(f.startswith("log-") for f in os.listdir(tmp_path))

    db = ShardedLogDB(str(tmp_path), num_shards=4)
    # flat files folded into partitions and removed from the root
    assert not any(f.startswith("log-") for f in os.listdir(tmp_path))
    for sid in (1, 5):
        ents = db.iterate_entries(sid, 1, 1, 4, 0)
        assert [e.index for e in ents] == [1, 2, 3]
    # shard 2 had a snapshot at index 1: migration keeps the live suffix
    # (snapshot.index+1 ..), exactly what restart-from-disk reads
    assert [e.index for e in db.iterate_entries(2, 1, 2, 4, 0)] == [2, 3]
    assert db.get_bootstrap_info(1, 1).addresses == {1: "x", 2: "y"}
    ss = db.get_snapshot(2, 1)
    assert ss is not None and ss.index == 1
    db.close()

    # and the migrated layout survives another restart
    db2 = ShardedLogDB(str(tmp_path), num_shards=4)
    assert [e.index for e in db2.iterate_entries(5, 1, 1, 4, 0)] == [1, 2, 3]
    db2.close()


def test_spanning_batch_save_and_snapshot_routing(tmp_path):
    """The device engine saves one [G]-lane batch covering many
    partitions in ONE call (engine/kernel_engine.py step loop)."""
    db = ShardedLogDB(str(tmp_path), num_shards=4)
    batch = [_update(shard=sid, n=2, commit=1) for sid in range(1, 33)]
    db.save_raft_state(batch, worker_id=0)
    for sid in range(1, 33):
        assert db.read_raft_state(sid, 1, 0).entry_count == 2
    db.save_snapshots([pb.Update(
        shard_id=sid, replica_id=1,
        snapshot=pb.Snapshot(index=2, term=1, shard_id=sid))
        for sid in range(1, 33)])
    db.close()
    db2 = ShardedLogDB(str(tmp_path), num_shards=4)
    for sid in range(1, 33):
        assert db2.get_snapshot(sid, 1).index == 2
    db2.close()


def test_remove_and_compact_route(tmp_path):
    db = ShardedLogDB(str(tmp_path), num_shards=4)
    for sid in (1, 2):
        db.save_raft_state([_update(shard=sid, n=6, commit=5)], worker_id=0)
    db.remove_entries_to(1, 1, 3)
    assert [e.index for e in db.iterate_entries(1, 1, 4, 7, 0)] == [4, 5, 6]
    assert db.iterate_entries(1, 1, 1, 7, 0) == []   # below the floor
    db.remove_node_data(2, 1)
    assert db.read_raft_state(2, 1, 0) is None
    infos = {ni.shard_id for ni in db.list_node_info()}
    assert infos == {1}
    db.close()


class _SlowFsyncFS:
    """OSFS wrapper whose fsync sleeps — makes overlap measurable."""

    def __init__(self, delay):
        from dragonboat_tpu.vfs import OSFS

        self._fs = OSFS()
        self.delay = delay
        self.fsyncs = 0
        self._mu = threading.Lock()

    def __getattr__(self, name):
        return getattr(self._fs, name)

    def fsync(self, f):
        with self._mu:
            self.fsyncs += 1
        time.sleep(self.delay)
        self._fs.fsync(f)


def test_fsyncs_overlap_across_partitions(tmp_path):
    """THE r3 VERDICT finding: with the single-file engine, W workers
    serialized on one lock+file. Two workers flushing different
    partitions must overlap their fsyncs (wall << 2 x serial)."""
    delay = 0.15
    fs = _SlowFsyncFS(delay)
    db = ShardedLogDB(str(tmp_path), num_shards=4, fs=fs)
    n_each = 4

    def worker(sid, wid):
        for i in range(n_each):
            db.save_raft_state(
                [_update(shard=sid, first=1 + 2 * i, n=2)], worker_id=wid)

    t0 = time.time()
    ts = [threading.Thread(target=worker, args=(sid, sid % 4))
          for sid in (1, 2, 3, 4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.time() - t0
    serial = 4 * n_each * delay     # what the single-lock engine would cost
    # four truly-concurrent streams should land near n_each * delay;
    # allow generous scheduler slack on the 1-core CI box
    assert wall < serial * 0.6, (wall, serial)
    db.close()


def test_crash_kill_recovery_all_partitions(tmp_path):
    """Kill the process image (skip close) after spanning writes; every
    partition must recover, including a torn tail in each partition."""
    db = ShardedLogDB(str(tmp_path), num_shards=4)
    for sid in range(1, 9):
        db.save_raft_state([_update(shard=sid, n=3, commit=2)], worker_id=0)
    # simulate the crash: no close(), then garble a torn tail onto every
    # partition's active file (an unsynced partial record)
    for i in range(4):
        pdir = os.path.join(tmp_path, f"part-{i:02d}")
        logs = sorted(f for f in os.listdir(pdir) if f.startswith("log-"))
        with open(os.path.join(pdir, logs[-1]), "ab") as f:
            f.write(b"\x02\x00NE\x7f")     # half a header
    db2 = ShardedLogDB(str(tmp_path), num_shards=4)
    for sid in range(1, 9):
        assert [e.index for e in db2.iterate_entries(sid, 1, 1, 4, 0)] == \
            [1, 2, 3]
    # and the recovered engine accepts new writes
    db2.save_raft_state([_update(shard=1, first=4, n=1)], worker_id=0)
    assert db2.read_raft_state(1, 1, 0).entry_count == 4
    db2.close()


def test_nodehost_default_is_sharded(tmp_path):
    from dragonboat_tpu.config import NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost

    nh = NodeHost(NodeHostConfig(
        node_host_dir=str(tmp_path / "nh"),
        raft_address="localhost:26000",
    ), auto_run=False)
    try:
        assert nh.logdb.name().startswith("sharded-tan")
        assert os.path.isdir(os.path.join(nh.env.logdb_dir and
                                          nh.env.logdb_dir, "part-00"))
    finally:
        nh.close()


def test_legacy_dir_flag_bumped_on_migration(tmp_path):
    """A flat-'tan' NodeHost dir migrates AND gets its flag rewritten to
    sharded-tan, so a rolled-back pre-sharding binary refuses the dir
    instead of silently starting from an empty log."""
    import json

    from dragonboat_tpu.config import NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.server.env import FLAG_FILENAME

    nh = NodeHost(NodeHostConfig(node_host_dir=str(tmp_path),
                                 raft_address="flag-1"), auto_run=False)
    nh.close()
    fp = None
    for dirpath, _, files in os.walk(tmp_path):
        if FLAG_FILENAME in files:
            fp = os.path.join(dirpath, FLAG_FILENAME)
            break
    assert fp is not None
    with open(fp) as f:
        assert json.load(f)["logdb_type"] == "sharded-tan"
    # simulate a legacy dir: rewrite the flag back to "tan"
    with open(fp) as f:
        saved = json.load(f)
    saved["logdb_type"] = "tan"
    with open(fp, "w") as f:
        json.dump(saved, f)
    nh2 = NodeHost(NodeHostConfig(node_host_dir=str(tmp_path),
                                  raft_address="flag-1"), auto_run=False)
    nh2.close()
    with open(fp) as f:
        assert json.load(f)["logdb_type"] == "sharded-tan"


# -- one log, one flush (PR 30) ---------------------------------------------


def _hist_count(name):
    from dragonboat_tpu import telemetry

    return telemetry.GLOBAL.snapshot().get(name, 0)


class _CountingFile:
    """Counts what the writer asks of its file."""

    def __init__(self, fs, f):
        self._fs = fs
        self._f = f

    def write(self, b):
        self._fs.calls["write"] += 1
        return self._f.write(b)

    def tell(self):
        self._fs.calls["tell"] += 1
        return self._f.tell()

    def __getattr__(self, name):
        return getattr(self._f, name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()
        return False


class _CountingFS:
    """OSFS wrapper that counts write / tell on append handles, fsyncs."""

    def __init__(self):
        from dragonboat_tpu.vfs import OSFS

        self._fs = OSFS()
        self.calls = {"write": 0, "tell": 0, "fsync": 0}

    def __getattr__(self, name):
        return getattr(self._fs, name)

    def open(self, path, mode="rb"):
        f = self._fs.open(path, mode)
        return _CountingFile(self, f) if "a" in mode else f

    def fsync(self, f):
        self.calls["fsync"] += 1
        self._fs.fsync(getattr(f, "_f", f))


def _assert_offset_is_the_files(db: TanLogDB):
    """The offset tan keeps is where the file really is."""
    with db._mu:
        kept = db._offset()
        assert kept == db._active.tell()
        db._active.flush()
        assert kept == os.path.getsize(db._path(db._active_fileno))


@pytest.mark.parametrize(
    "event", ["appends", "rotation", "reopen", "write_error", "fsync_error"])
def test_kept_offset_is_the_files_position(tmp_path, event):
    """tan's writer keeps the active file's offset itself (no tell() a
    record): it must equal the file's position whatever happened last."""
    from dragonboat_tpu.vfs import OSFS, ErrorFS, InjectedError

    failing = set()
    fs = ErrorFS(OSFS(), inject=lambda op, path: op in failing)
    db = TanLogDB(str(tmp_path), max_file_size=2048, fs=fs)
    db.save_bootstrap_info(1, 1, pb.Bootstrap(addresses={1: "a"}))
    db.save_raft_state([_update(shard=s, n=2) for s in (1, 2, 3)], 0)
    _assert_offset_is_the_files(db)
    nxt = 3
    if event == "rotation":
        first_file = db._active_fileno
        while db._active_fileno == first_file:
            db.save_raft_state([_update(first=nxt, n=3)], 0)
            nxt += 3
        assert 0 < db._offset() <= 2048
    elif event == "reopen":
        db.close()
        db = TanLogDB(str(tmp_path), max_file_size=2048, fs=fs)
        assert db._offset() > 0          # resumed at the end, not at 0
    elif event in ("write_error", "fsync_error"):
        failing.add(event.split("_")[0])
        with pytest.raises(InjectedError):
            db.save_raft_state([_update(first=nxt, n=3)], 0)
        failing.clear()
        assert db._off is None           # not trusted until re-read
    _assert_offset_is_the_files(db)
    # and the next record is indexed where it really landed
    db.save_raft_state([_update(first=nxt, n=3)], 0)
    _assert_offset_is_the_files(db)
    assert [e.index for e in db.iterate_entries(1, 1, nxt, nxt + 3, 0)] == \
        [nxt, nxt + 1, nxt + 2]
    db.close()
    db2 = TanLogDB(str(tmp_path), max_file_size=2048)
    assert db2.read_raft_state(1, 1, 0).entry_count == nxt + 2
    assert db2.get_bootstrap_info(1, 1).addresses == {1: "a"}
    db2.close()


def test_oversized_batch_goes_whole_into_an_empty_file(tmp_path):
    db = TanLogDB(str(tmp_path), max_file_size=512)
    db.save_raft_state([_update(first=1, n=2)], 0)
    first_file = db._active_fileno
    db.save_raft_state([_update(shard=s, n=4) for s in range(1, 13)], 0)
    # rotated once, and the whole batch sits in the one new file
    assert db._active_fileno == first_file + 1
    assert db._offset() > 512
    for s in range(1, 13):
        assert len(db.iterate_entries(s, 1, 1, 5, 0)) == 4
    db.close()


def test_default_dir_saves_a_round_in_one_write_one_fsync(tmp_path):
    """An engine round's save at 48 shards on a NEW default directory:
    one partition, no pool, no tell(), one write, one fsync."""
    fs = _CountingFS()
    db = ShardedLogDB(str(tmp_path), fs=fs)
    assert db.num_shards == 1 and db.name() == "sharded-tan-1"
    batch = [_update(shard=s, first=1, n=5, commit=3) for s in range(1, 49)]
    before = dict(fs.calls)
    parts0 = _hist_count("logdb.save_parts.count")
    sum0 = _hist_count("logdb.save_parts.sum")
    fsyncs0 = _hist_count("logdb.fsync_us.count")
    db.save_raft_state(batch, worker_id=0)
    assert {k: fs.calls[k] - before[k] for k in before} == \
        {"write": 1, "tell": 0, "fsync": 1}
    assert _hist_count("logdb.save_parts.count") - parts0 == 1
    assert _hist_count("logdb.save_parts.sum") - sum0 == 1
    assert _hist_count("logdb.fsync_us.count") - fsyncs0 == 1
    assert not any(t.name.startswith("tanshard-flush")
                   for t in threading.enumerate())
    db.close()
    db2 = ShardedLogDB(str(tmp_path))
    for s in range(1, 49):
        rs = db2.read_raft_state(s, 1, 0)
        assert rs.entry_count == 5 and rs.state.commit == 3
    db2.close()


def test_batch_cut_anywhere_recovers_its_whole_frame_prefix(tmp_path):
    """A batch is one write of many frames, each with its own header and
    CRC: a crash at ANY byte of the write leaves exactly the frames that
    landed whole, and the torn one is truncated away."""
    from dragonboat_tpu.logdb.tan import R_UPDATE, _enc_update, _frame

    src = tmp_path / "src"
    db = TanLogDB(str(src))
    db.save_raft_state([_update(shard=9, n=1)], 0)      # an earlier save
    batch = [_update(shard=s, n=1 + s % 3, commit=1) for s in range(1, 6)]
    path = db._path(db._active_fileno)
    base = db._offset()
    db.save_raft_state(batch, 0)
    db.close()
    with open(path, "rb") as f:
        whole = f.read()
    ends, off = [], base
    for ud in batch:
        off += len(_frame(R_UPDATE, ud.shard_id, ud.replica_id,
                          _enc_update(ud)))
        ends.append(off)
    assert off == len(whole)
    for cut in range(base, len(whole) + 1):
        d = tmp_path / f"cut{cut}"
        os.makedirs(d)
        with open(d / os.path.basename(path), "wb") as f:
            f.write(whole[:cut])
        got = TanLogDB(str(d))
        landed = sum(1 for e in ends if e <= cut)
        assert got.read_raft_state(9, 1, 0).entry_count == 1
        for k, ud in enumerate(batch):
            rs = got.read_raft_state(ud.shard_id, 1, 0)
            if k < landed:
                assert rs.entry_count == len(ud.entries_to_save), (cut, k)
            else:
                assert rs is None, (cut, k)
        # the torn tail is gone: the next append starts on a frame edge
        assert got._offset() == ([base] + ends)[landed]
        got.close()


def test_writers_on_one_log_share_fsyncs(tmp_path):
    """Host step workers that meet at one partition append while one of
    them is in fsync, and the next fsync covers them all (group commit):
    N writers finish in well under N x serial, and nothing is lost."""
    import sys

    delay = 0.05
    fs = _SlowFsyncFS(delay)
    db = ShardedLogDB(str(tmp_path), fs=fs)
    assert db.num_shards == 1
    n_threads, n_each = 8, 6
    shared0 = _hist_count("logdb.sync_shared")
    fsyncs0 = fs.fsyncs
    errors = []

    def worker(sid):
        try:
            for i in range(n_each):
                db.save_raft_state(
                    [_update(shard=sid, first=1 + 2 * i, n=2, commit=2 * i)],
                    worker_id=sid)
        except Exception as e:            # surfaced below, not swallowed
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        t0 = time.time()
        ts = [threading.Thread(target=worker, args=(sid,))
              for sid in range(1, n_threads + 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        wall = time.time() - t0
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in ts)
    assert errors == []
    # one lock, no queue discipline: a covered writer can sit behind an
    # uncovered one's fsync, so an fsync covers ~2-3 saves here, not all 8
    # (measured 0.9-1.1 s against 2.4 serial, 18-23 fsyncs for 48 saves)
    serial = n_threads * n_each * delay
    assert wall < serial * 0.75, (wall, serial)
    assert fs.fsyncs - fsyncs0 <= n_threads * n_each * 0.75
    assert _hist_count("logdb.sync_shared") - shared0 > 0
    db.close()
    db2 = ShardedLogDB(str(tmp_path))
    for sid in range(1, n_threads + 1):
        ents = db2.iterate_entries(sid, 1, 1, 2 * n_each + 1, 0)
        assert [e.index for e in ents] == list(range(1, 2 * n_each + 1))
        assert ents[-1].cmd == f"s{sid}e{2 * n_each}".encode()
    db2.close()


def test_a_shared_fsync_never_acknowledges_an_uncovered_append(tmp_path):
    """The fsync a writer returns on must have STARTED after its append:
    the moment a save returns, its record is in what a power loss would
    leave (MemFS: the content as of the last fsync)."""
    import sys

    from dragonboat_tpu.logdb.tan import R_UPDATE, _enc_update, _frame
    from dragonboat_tpu.vfs import MemFS

    class StartCoversFS(MemFS):
        """An fsync makes durable what the file held when it STARTED,
        and takes a while: what is appended meanwhile is not covered."""

        def fsync(self, f):
            super().fsync(f)
            time.sleep(0.002)

    fs = StartCoversFS()
    db = ShardedLogDB("/grp", fs=fs)
    n_each = 60
    shared0 = _hist_count("logdb.sync_shared")
    errors = []

    def worker(sid):
        try:
            for i in range(n_each):
                ud = _update(shard=sid, first=1 + i, n=1)
                db.save_raft_state([ud], sid)
                rec = _frame(R_UPDATE, sid, 1, _enc_update(ud))
                assert any(rec in n.synced for n in list(fs._files.values())), \
                    f"shard {sid} index {1 + i} acknowledged before its fsync"
        except Exception as e:
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        ts = [threading.Thread(target=worker, args=(sid,))
              for sid in (1, 2, 3, 4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in ts)
    assert errors == []
    assert _hist_count("logdb.sync_shared") > shared0
    fs.crash()                  # no close: only what was fsynced remains
    got = ShardedLogDB("/grp", fs=fs)
    for sid in (1, 2, 3, 4):
        assert got.read_raft_state(sid, 1, 0).entry_count == n_each
    got.close()


@pytest.mark.parametrize("case", ["new", "marker16", "mismatch"])
def test_default_geometry(tmp_path, case):
    """shards unset: a new directory is ONE log, an existing directory
    keeps what its marker pins, an explicit mismatch still refuses."""
    if case == "new":
        db = ShardedLogDB(str(tmp_path))
        assert db.num_shards == 1
        assert ShardedLogDB.stored_shard_count(str(tmp_path), db.fs) == 1
        assert sorted(d for d in os.listdir(tmp_path)
                      if d.startswith("part-")) == ["part-00"]
        db.close()
        return
    old = ShardedLogDB(str(tmp_path), num_shards=16)
    old.save_raft_state([_update(shard=s, n=3, commit=2)
                         for s in range(1, 49)], worker_id=0)
    old.close()
    if case == "marker16":
        db = ShardedLogDB(str(tmp_path))
        assert db.num_shards == 16 and db.name() == "sharded-tan-16"
        for s in range(1, 49):
            assert db.read_raft_state(s, 1, 0).entry_count == 3
        # and it still writes across its partitions
        parts0 = _hist_count("logdb.save_parts.sum")
        db.save_raft_state([_update(shard=s, first=4, n=1)
                            for s in range(1, 49)], worker_id=0)
        assert _hist_count("logdb.save_parts.sum") - parts0 == 16
        db.close()
    else:
        with pytest.raises(ShardGeometryError):
            ShardedLogDB(str(tmp_path), num_shards=1)
        with pytest.raises(ValueError):
            ShardedLogDB(str(tmp_path), num_shards=-1)


def test_nodehost_default_dir_is_one_log_and_old_dirs_keep_theirs(tmp_path):
    from dragonboat_tpu.config import (
        ExpertConfig, LogDBConfig, NodeHostConfig)
    from dragonboat_tpu.nodehost import NodeHost

    def host(d, **kw):
        return NodeHost(NodeHostConfig(
            node_host_dir=str(tmp_path / d), raft_address="geo-1", **kw),
            auto_run=False)

    nh = host("new")
    try:
        assert nh.logdb.name() == "sharded-tan-1"
    finally:
        nh.close()
    pinned = ExpertConfig(logdb=LogDBConfig(shards=16))
    nh = host("old", expert=pinned)
    nh.close()
    nh = host("old")                      # reopened with the default
    try:
        assert nh.logdb.name() == "sharded-tan-16"
    finally:
        nh.close()
    with pytest.raises(ShardGeometryError):
        host("old", expert=ExpertConfig(logdb=LogDBConfig(shards=4)))

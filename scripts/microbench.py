"""Host-path microbenchmarks — parity with the reference's in-tree suite
(benchmark_test.go: marshaling :244, SaveRaftState 16/128/1024B :361,
fsync latency :276, RSM step with/without sessions :618, transport echo
:508, chunk writer :649; run via `make benchmark`).

Usage: python scripts/microbench.py [quick]
Prints one JSON line per benchmark: {"bench", "value", "unit"}.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# a CPU-only analysis tool: host-path microbenchmarks, never a device number
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def out(bench: str, value: float, unit: str, **extra) -> None:
    print(json.dumps({"bench": bench, "value": round(value, 1),
                      "unit": unit, **extra}), flush=True)


def timeit(fn, n: int, min_s: float = 0.5):
    fn()  # warmup
    reps = 0
    t0 = time.perf_counter()
    while True:
        fn()
        reps += 1
        dt = time.perf_counter() - t0
        if dt >= min_s:
            return reps * n / dt


def bench_marshaling(quick):
    from dragonboat_tpu import raftpb as pb

    msgs = tuple(
        pb.Message(type=pb.MessageType.REPLICATE, from_=1, to=2, shard_id=7,
                   term=3, log_term=3, log_index=i, commit=i,
                   entries=(pb.Entry(term=3, index=i + 1, cmd=b"k" * 16),))
        for i in range(64)
    )
    batch = pb.MessageBatch(requests=msgs, deployment_id=1,
                            source_address="bench-host-1")
    enc = pb.encode_message_batch(batch)
    min_s = 0.2 if quick else 0.5
    out("marshal MessageBatch (64 msgs, 16B)",
        timeit(lambda: pb.encode_message_batch(batch), 64, min_s), "msgs/s")
    out("unmarshal MessageBatch (64 msgs, 16B)",
        timeit(lambda: pb.decode_message_batch(enc), 64, min_s), "msgs/s")


def bench_save_raft_state(quick):
    from dragonboat_tpu import raftpb as pb
    from dragonboat_tpu.logdb.tan import TanLogDB

    for size in (16, 128, 1024):
        with tempfile.TemporaryDirectory() as d:
            db = TanLogDB(d)
            i = [0]

            def one():
                base = i[0] * 48
                ud = pb.Update(
                    shard_id=1, replica_id=1,
                    state=pb.State(term=1, vote=1, commit=base),
                    entries_to_save=tuple(
                        pb.Entry(term=1, index=base + j + 1, cmd=b"x" * size)
                        for j in range(48)),
                )
                db.save_raft_state([ud], 0)  # batch of 48 + ONE fsync
                i[0] += 1

            out(f"SaveRaftState {size}B x48/batch (tan, fsync)",
                timeit(one, 48, 0.3 if quick else 1.0), "entries/s")
            db.close()


def bench_fsync(quick):
    with tempfile.TemporaryDirectory() as d:
        f = open(os.path.join(d, "probe"), "ab")

        def one():
            f.write(b"x" * 512)
            f.flush()
            os.fsync(f.fileno())

        n = 50 if quick else 200
        t0 = time.perf_counter()
        for _ in range(n):
            one()
        out("fsync latency (512B append)",
            (time.perf_counter() - t0) / n * 1e6, "us")
        f.close()


def bench_rsm_step(quick):
    from dragonboat_tpu import raftpb as pb
    from dragonboat_tpu.rsm.statemachine import StateMachine

    class KV:
        def __init__(self):
            self.d = {}

        def update(self, e):
            from dragonboat_tpu.statemachine import Result

            k, v = e.cmd.split(b"=", 1)
            self.d[k] = v
            return Result(value=len(self.d))

        def lookup(self, q):
            return self.d.get(q)

        def save_snapshot(self, w, fc, done):
            pass

        def recover_from_snapshot(self, r, files, done):
            pass

        def close(self):
            pass

    from dragonboat_tpu.statemachine import IStateMachine

    IStateMachine.register(KV)

    for sessions in (False, True):
        sm = StateMachine(1, 1, KV())
        if sessions:
            # RegisterClientID entry (client.go session registration)
            sm.handle([pb.Entry(term=1, index=1, client_id=77,
                                series_id=pb.SERIES_ID_FOR_REGISTER,
                                cmd=b"")])
        i = [2]

        def one():
            base = i[0]
            ents = [
                pb.Entry(term=1, index=base + j,
                         client_id=(77 if sessions else 0),
                         series_id=((base + j) if sessions else 0),
                         # real clients acknowledge as they go; keeps the
                         # session response cache bounded
                         responded_to=((base + j - 1) if sessions else 0),
                         cmd=b"key%d=val" % (j % 97))
                for j in range(64)
            ]
            sm.handle(ents)
            i[0] += 64

        label = "with sessions" if sessions else "no-op session"
        out(f"RSM step 64/batch ({label})",
            timeit(one, 64, 0.2 if quick else 0.5), "entries/s")


def bench_transport_echo(quick):
    from dragonboat_tpu import raftpb as pb
    from dragonboat_tpu.transport.chan import ChanTransport

    got = [0]

    def handler(batch):
        got[0] += len(batch.requests)

    t1 = ChanTransport("echo-a", handler, lambda c: True)
    t2 = ChanTransport("echo-b", handler, lambda c: True)
    t1.start()
    t2.start()
    conn = t1.get_connection("echo-b")
    batch = pb.MessageBatch(
        requests=tuple(
            pb.Message(type=pb.MessageType.HEARTBEAT, from_=1, to=2,
                       shard_id=1, term=1) for _ in range(64)),
        deployment_id=0, source_address="echo-a")
    out("chan transport send (64-msg batch)",
        timeit(lambda: conn.send_message_batch(batch), 64,
               0.2 if quick else 0.5), "msgs/s")
    t1.close()
    t2.close()


def bench_chunk_writer(quick):
    from dragonboat_tpu.rsm.chunkwriter import ChunkWriter

    sink = []

    def one():
        sink.clear()
        cw = ChunkWriter(sink.append, shard_id=1, to_replica=2, from_=1,
                         deployment_id=0, chunk_size=256 * 1024)
        from dragonboat_tpu import raftpb as pb

        cw.message = pb.Message(type=pb.MessageType.INSTALL_SNAPSHOT,
                                from_=1, to=2, shard_id=1)
        block = b"z" * 65536
        for _ in range(16):  # 1 MiB image
            cw.write(block)
        cw.close()

    out("ChunkWriter stream (1MiB image)",
        timeit(one, 1 << 20, 0.3 if quick else 1.0), "bytes/s")


def bench_native_scan(quick):
    import struct
    import zlib

    from dragonboat_tpu import native
    from dragonboat_tpu.logdb.tan import MAGIC

    payload = b"p" * 200
    frame = struct.pack("<III", MAGIC, len(payload),
                        zlib.crc32(payload)) + payload
    buf = frame * 5000  # ~1MB log image

    min_s = 0.2 if quick else 0.5
    label = "C" if native.available() else "no-native: py"
    out(f"tan replay scan ({label})",
        timeit(lambda: native.tan_scan(buf, MAGIC), len(buf), min_s),
        "bytes/s")
    out("tan replay scan (py reference)",
        timeit(lambda: native._tan_scan_py(buf, MAGIC), len(buf), min_s),
        "bytes/s")


if __name__ == "__main__":
    quick = len(sys.argv) > 1 and sys.argv[1] == "quick"
    bench_marshaling(quick)
    bench_save_raft_state(quick)
    bench_fsync(quick)
    bench_rsm_step(quick)
    bench_transport_echo(quick)
    bench_chunk_writer(quick)
    bench_native_scan(quick)

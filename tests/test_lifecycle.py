"""Proposal-lifecycle tracing (PR 7): tracer units, Chrome export,
/trace endpoint, and end-to-end spans on both engine pipeline depths.

The tracer is process-global (like flight.RECORDER), so every test
snapshots/restores its configuration and ring via the autouse fixture.
"""

import json
import time
import urllib.request

import pytest

from dragonboat_tpu import flight, lifecycle, telemetry
from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
from dragonboat_tpu.lifecycle import (
    LifecycleTracer,
    STAGES,
    validate_chrome_trace,
)
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.request import LogicalClock, PendingProposal

from test_kernel_engine import close_all, propose_retry
from test_nodehost import KVStateMachine, wait_leader


@pytest.fixture(autouse=True)
def _isolate_global_tracer():
    """The module tracer is process-global; leave it as we found it and
    empty between tests (NodeHost construction reconfigures it)."""
    t = lifecycle.TRACER
    before = (t._every, t._slow_us)
    t.reset()
    yield
    t.configure(sample_every=before[0], slow_commit_us=before[1])
    t.reset()


def make_tracer(**kw):
    """Fully-isolated tracer: injected counting clock, private registry
    and recorder (the GLOBAL ones must not see test samples)."""
    kw.setdefault("sample_every", 1)
    kw.setdefault("clock", iter(range(0, 10_000_000, 10)).__next__)
    kw.setdefault("registry", telemetry.Registry())
    kw.setdefault("recorder", flight.FlightRecorder(capacity=16))
    return LifecycleTracer(**kw)


# -- tracer units -----------------------------------------------------------

def test_sampling_is_deterministic_one_in_n():
    t = make_tracer(sample_every=4)
    assert [k for k in range(1, 17) if t.sampled(k)] == [4, 8, 12, 16]
    # off switch: 0 disables everything
    t.configure(sample_every=0)
    assert not t.enabled
    assert not t.sampled(4)
    assert not t.begin(4)


def test_span_lifecycle_and_ring():
    t = make_tracer()
    assert t.begin(1, shard_id=7)
    assert not t.begin(1)          # duplicate key refused
    t.stamp(1, lifecycle.STAGE_STAGE)
    t.stamp(1, lifecycle.STAGE_DISPATCH)
    t.finish(1)
    t.finish(1)                    # double finish is a no-op
    traces = t.completed()
    assert len(traces) == 1
    tr = traces[0]
    assert tr["key"] == 1 and tr["shard_id"] == 7
    assert [s for s, _ in tr["stamps"]] == [
        "propose", "stage", "dispatch", "ack"]
    ts = [x for _, x in tr["stamps"]]
    assert ts == sorted(ts)
    assert tr["total_us"] == ts[-1] - ts[0]
    assert t.counts() == {"active": 0, "finished": 1, "scrubbed": 0,
                          "dropped": 0, "overwritten": 0}


def test_ring_is_bounded():
    t = make_tracer(ring_size=2)
    for k in (1, 2, 3):
        t.begin(k)
        t.finish(k)
    keys = [tr["key"] for tr in t.completed()]
    assert keys == [2, 3]          # oldest evicted
    assert t.counts()["overwritten"] == 1      # ... and nobody had read it


def test_default_ring_holds_a_polling_window():
    assert lifecycle.TRACER._ring._q.maxlen == lifecycle.DEFAULT_RING_SIZE
    assert lifecycle.DEFAULT_RING_SIZE == 4096


def test_drain_returns_and_clears_and_loss_is_counted():
    """A draining poller sees each trace once; ``overwritten`` counts
    only traces the ring pushed out before any reader saw them."""
    t = make_tracer(ring_size=3)

    def commit(*keys):
        for k in keys:
            t.begin(k)
            t.finish(k)

    commit(1, 2)
    assert [tr["key"] for tr in t.drain()] == [1, 2]
    assert t.drain() == [] and t.completed() == []
    commit(3, 4, 5)
    assert [tr["key"] for tr in t.completed()] == [3, 4, 5]   # a poll
    commit(6)                      # pushes out 3, which the poll had read
    assert t.counts()["overwritten"] == 0
    commit(7, 8, 9)                # 6 goes unread, 4 and 5 were read
    assert t.counts()["overwritten"] == 1
    assert [tr["key"] for tr in t.drain()] == [7, 8, 9]
    assert t.counts()["finished"] == 9
    t.reset()
    assert t.counts()["overwritten"] == 0


def test_active_cap_refuses_not_grows():
    t = make_tracer(max_active=2)
    assert t.begin(1) and t.begin(2)
    assert not t.begin(3)          # at cap: counted, refused
    assert t.active_count() == 2
    assert t.counts()["dropped"] == 1
    t.finish(3)                    # never opened -> no trace
    assert len(t.completed()) == 0


def test_scrub_discards_without_sinking():
    t = make_tracer()
    t.begin(5)
    t.stamp(5, lifecycle.STAGE_STAGE)
    t.scrub(5)
    t.stamp(5, lifecycle.STAGE_DISPATCH)   # post-scrub stamp: no-op
    t.finish(5)                            # post-scrub finish: no-op
    assert t.completed() == []
    c = t.counts()
    assert c["scrubbed"] == 1 and c["finished"] == 0 and c["active"] == 0


def test_stage_histograms_fed_on_finish():
    reg = telemetry.Registry()
    t = make_tracer(registry=reg)
    t.begin(1)
    t.stamp(1, lifecycle.STAGE_STAGE)
    t.stamp(1, lifecycle.STAGE_DISPATCH)
    t.finish(1)
    fams = telemetry.parse_exposition(reg.exposition())
    samples = fams["commit_stage_us"]["samples"]
    by_label = {lb.get("stage"): v for nm, lb, v in samples
                if nm.endswith("_count")}
    # one observation per consecutive stamp pair, labeled by the LATER
    # stage, plus the propose->ack total
    assert by_label == {"stage": 1, "dispatch": 1, "ack": 1, "total": 1}
    sums = {lb.get("stage"): v for nm, lb, v in samples
            if nm.endswith("_sum")}
    assert sums["total"] == 30     # 3 clock ticks of 10us


def test_slow_commit_flight_event():
    rec = flight.FlightRecorder(capacity=8)
    t = make_tracer(slow_commit_us=25, recorder=rec)
    t.begin(1)                     # fast: 1 delta of 10us < 25
    t.finish(1)
    t.begin(2)
    t.stamp(2, lifecycle.STAGE_STAGE)
    t.stamp(2, lifecycle.STAGE_DISPATCH)
    t.finish(2)                    # 30us >= 25: slow
    recs = rec.tail()
    assert len(recs) == 1
    r = recs[0]
    assert r["kind"] == flight.SLOW_COMMIT
    assert r["key"] == 2 and r["total_us"] == 30 and r["slo_us"] == 25
    # full breakdown, offsets from the propose stamp, monotone
    assert [s for s, _ in r["stages"]] == [
        "propose", "stage", "dispatch", "ack"]
    offs = [o for _, o in r["stages"]]
    assert offs[0] == 0 and offs == sorted(offs)
    # the record must survive the recorder's canonical JSON dump
    json.loads(rec.dump_json())


def test_slow_commit_disabled_by_default():
    rec = flight.FlightRecorder(capacity=8)
    t = make_tracer(recorder=rec)
    t.begin(1)
    t.stamp(1, lifecycle.STAGE_DISPATCH)
    t.finish(1)
    assert rec.tail() == []


# -- Chrome-trace export + validator ---------------------------------------

def test_export_chrome_trace_round_trips_validator():
    t = make_tracer()
    for k in (1, 2):
        t.begin(k, shard_id=k)
        t.stamp(k, lifecycle.STAGE_STAGE)
        t.stamp(k, lifecycle.STAGE_DISPATCH)
        t.stamp(k, lifecycle.STAGE_RETIRE)
        t.finish(k)
    obj = json.loads(json.dumps(t.export_chrome_trace()))
    assert validate_chrome_trace(obj) == 10    # 2 spans x 5 events
    ev = obj["traceEvents"][0]
    assert ev["ph"] == "X" and ev["name"] == "propose"
    assert ev["pid"] == 1 and ev["tid"] == 1
    # dur chains: each event ends where the next begins
    span1 = [e for e in obj["traceEvents"] if e["tid"] == 1]
    for a, b in zip(span1, span1[1:]):
        assert a["ts"] + a["dur"] == b["ts"]
    # device-capture stitching names ride in args
    dispatch = next(e for e in span1 if e["name"] == "dispatch")
    assert dispatch["args"]["annotation"] == "kernel_engine.step"
    retire = next(e for e in span1 if e["name"] == "retire")
    assert retire["args"]["annotation"] == "kernel_engine.process_outputs"


def test_validator_rejections():
    ok = {"name": "propose", "ph": "X", "ts": 1, "dur": 1,
          "pid": 0, "tid": 1}
    # bare-array form accepted
    assert validate_chrome_trace([ok]) == 1
    with pytest.raises(ValueError, match="object or array"):
        validate_chrome_trace("nope")
    with pytest.raises(ValueError, match="traceEvents must be an array"):
        validate_chrome_trace({"traceEvents": 3})
    for missing in ("name", "ph", "ts", "pid", "tid"):
        bad = dict(ok)
        del bad[missing]
        with pytest.raises(ValueError, match=f"missing required key "
                                             f"'{missing}'"):
            validate_chrome_trace([bad])
    with pytest.raises(ValueError, match="non-negative"):
        validate_chrome_trace([dict(ok, ts=-1)])
    with pytest.raises(ValueError, match="non-negative"):
        validate_chrome_trace([dict(ok, dur=-2)])
    # backwards time WITHIN one (pid, tid) span
    with pytest.raises(ValueError, match="backwards"):
        validate_chrome_trace([dict(ok, ts=10), dict(ok, ts=5)])
    # different spans may interleave freely
    assert validate_chrome_trace(
        [dict(ok, ts=10), dict(ok, ts=5, tid=2)]) == 2


# -- request-book integration ----------------------------------------------

class _Session:
    client_id = 1
    series_id = 1
    responded_to = 0


def test_book_begins_finishes_and_scrubs_spans():
    t = lifecycle.TRACER
    t.configure(sample_every=1)
    book = PendingProposal(clock=LogicalClock(), shard_id=3)

    rs, entry = book.propose(_Session(), b"x", timeout_ticks=100)
    assert t.active_count() == 1
    from dragonboat_tpu.statemachine import Result

    book.applied(entry.key, 1, 1, Result(), rejected=False)
    assert rs.wait(1).completed()
    assert t.active_count() == 0
    tr = t.completed()[-1]
    assert tr["key"] == entry.key and tr["shard_id"] == 3

    # dropped -> scrub, not a trace
    _, e2 = book.propose(_Session(), b"y", timeout_ticks=100)
    book.dropped(e2.key)
    assert t.active_count() == 0
    assert all(x["key"] != e2.key for x in t.completed())

    # timeout GC -> scrub
    _, e3 = book.propose(_Session(), b"z", timeout_ticks=1)
    book.advance()
    book.advance()
    book.gc()
    assert t.active_count() == 0

    # terminate_all -> scrub
    book.propose(_Session(), b"w", timeout_ticks=100)
    book.terminate_all()
    assert t.active_count() == 0
    assert t.counts()["scrubbed"] == 3


# -- /trace endpoint --------------------------------------------------------

def test_trace_endpoint_serves_chrome_json():
    from dragonboat_tpu.server.metrics_http import MetricsServer

    t = make_tracer()
    t.begin(1)
    t.stamp(1, lifecycle.STAGE_DISPATCH)
    t.finish(1)
    srv = MetricsServer([telemetry.Registry()], tracer=t)
    try:
        with urllib.request.urlopen(
                f"http://{srv.address}/trace", timeout=5) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            obj = json.loads(resp.read().decode("utf-8"))
    finally:
        srv.close()
    # the endpoint also merges compile spans from the process-wide
    # capacity tracker and the engines' rounds — any earlier live engine
    # in this process may have left some; the lifecycle spans must ride
    # beside them
    others = [e for e in obj["traceEvents"]
              if e.get("cat") in ("compile", "round")]
    assert validate_chrome_trace(obj) == 3 + len(others)
    assert [e["name"] for e in obj["traceEvents"]
            if e not in others] == ["propose", "dispatch", "ack"]


def test_trace_endpoint_serves_rounds_beside_spans_on_one_clock():
    """/trace carries the engines' rounds (one row per engine) beside the
    proposal rows, on the spans' clock, and the strict validator accepts
    the merged timeline."""
    from dragonboat_tpu import tracing
    from dragonboat_tpu.events import Metrics
    from dragonboat_tpu.server.metrics_http import MetricsServer

    ticks = iter(range(1_000, 10_000_000, 10))      # one clock, in us
    t = make_tracer(clock=ticks.__next__)
    rt = tracing.RoundTimer(Metrics(), "engine.t", engine="host-x",
                            registry=telemetry.Registry(),
                            clock_ns=lambda: next(ticks) * 1000,
                            cpu_clock_ns=lambda: 0)
    tracing.ROUNDS.reset()
    try:
        t.begin(1)
        rt.begin()
        t.stamp(1, lifecycle.STAGE_STAGE)
        rt.enter("upload")
        t.stamp(1, lifecycle.STAGE_DISPATCH)
        rt.enter("fetch")
        rt.commit(keys=[1])
        t.finish(1)
        srv = MetricsServer([telemetry.Registry()], tracer=t)
        try:
            with urllib.request.urlopen(
                    f"http://{srv.address}/trace", timeout=5) as resp:
                obj = json.loads(resp.read().decode("utf-8"))
        finally:
            srv.close()
    finally:
        tracing.ROUNDS.reset()
    assert validate_chrome_trace(obj) == len(obj["traceEvents"])
    rounds = [e for e in obj["traceEvents"] if e.get("cat") == "round"]
    assert [(e["name"], e["pid"], e["tid"]) for e in rounds] == [
        (p, "engine", "host-x") for p in ("stage", "upload", "fetch")]
    assert rounds[0]["args"]["keys"] == [1]
    spans = {e["name"]: e["ts"] for e in obj["traceEvents"]
             if e.get("cat") == "proposal"}
    # interleaved as they happened: both sides read the one clock
    assert (spans["propose"] < rounds[0]["ts"] < spans["stage"]
            < rounds[1]["ts"] < spans["dispatch"] < rounds[2]["ts"]
            < spans["ack"])


# -- end-to-end: spans across the engines ----------------------------------

def _traced_expert(depth):
    return ExpertConfig(kernel_log_cap=256, kernel_capacity=8,
                        kernel_apply_batch=16,
                        kernel_compaction_overhead=16,
                        kernel_pipeline_depth=depth,
                        trace_sample_every=1)


def _make_traced_cluster(prefix, depth):
    addrs = {i: f"{prefix}-{i}" for i in range(1, 4)}
    hosts = {}
    for rid, addr in addrs.items():
        nh = NodeHost(NodeHostConfig(raft_address=addr, rtt_millisecond=5,
                                     expert=_traced_expert(depth)))
        cfg = Config(shard_id=1, replica_id=rid, election_rtt=10,
                     heartbeat_rtt=2, compaction_overhead=5,
                     device_resident=True)
        nh.start_replica(addrs, False, KVStateMachine, cfg)
        hosts[rid] = nh
    return hosts


def _wait_full_trace(min_stages, timeout=30):
    """Poll the global ring for a completed trace with >= min_stages
    DISTINCT stages; returns it."""
    deadline = time.time() + timeout
    best = None
    while time.time() < deadline:
        for tr in lifecycle.TRACER.completed():
            stages = {s for s, _ in tr["stamps"]}
            if best is None or len(stages) > len({s for s, _ in
                                                  best["stamps"]}):
                best = tr
            if len(stages) >= min_stages:
                return tr
        time.sleep(0.1)
    raise AssertionError(
        f"no trace with >= {min_stages} distinct stages; best: "
        f"{best and [s for s, _ in best['stamps']]}")


@pytest.mark.parametrize("depth", [0, 1], ids=["serial", "pipelined"])
def test_e2e_trace_spans_kernel_commit_path(depth):
    """Acceptance: a sampled proposal's completed trace crosses >= 6
    distinct stages with monotone timestamps, on both the serial and
    the pipelined (one-step-late retirement) engine loops."""
    hosts = _make_traced_cluster(f"lc{depth}", depth)
    try:
        assert lifecycle.TRACER.enabled    # NodeHost wired the config
        lead = wait_leader(hosts, timeout=30)
        nh = hosts[lead]
        sess = nh.get_noop_session(1)
        for i in range(8):
            propose_retry(nh, sess, f"t{i}=v{i}".encode())
        tr = _wait_full_trace(min_stages=6)
        names = [s for s, _ in tr["stamps"]]
        ts = [x for _, x in tr["stamps"]]
        assert names[0] == "propose" and names[-1] == "ack"
        assert len(set(names)) >= 6
        assert all(s in STAGES for s in names)
        # the kernel commit path in full
        for want in ("propose", "stage", "dispatch", "retire", "ack"):
            assert want in names, (want, names)
        assert ts == sorted(ts), "stage stamps must be monotone"
        # exported ring round-trips the strict validator
        obj = json.loads(json.dumps(
            lifecycle.TRACER.export_chrome_trace()))
        assert validate_chrome_trace(obj) > 0
        # acked sampled spans drain; nothing leaks in the span book
        deadline = time.time() + 10
        while time.time() < deadline and lifecycle.TRACER.active_count():
            time.sleep(0.1)
    finally:
        close_all(hosts)
    assert lifecycle.TRACER.active_count() == 0


#: a served write's and a linearizable read's stamp lists on a one-replica
#: device-resident shard, as the tree before the round timer stamped them
#: (PR 24's, read off that commit): the benchmark's dwell readers take
#: "the stamp before", so a stamp added between two of these would move
#: ``dispatch_ms`` or ``read_quorum_ms`` without a word
GOLDEN_WRITE = {
    "memory": ["propose", "stage", "dispatch", "retire", "save", "apply",
               "ack"],
    "durable": ["propose", "stage", "dispatch", "retire", "save", "fsync",
                "apply", "ack"],
}
GOLDEN_READ = ["read_propose", "read_quorum", "read_serve"]


@pytest.mark.parametrize("logdb", ["memory", "durable"])
def test_stamp_lists_are_what_they_were_and_the_round_names_the_key(
        logdb, tmp_path):
    from dragonboat_tpu import tracing

    addr = f"gold-{logdb}"
    kw = {"node_host_dir": str(tmp_path)} if logdb == "durable" else {}
    nh = NodeHost(NodeHostConfig(raft_address=addr, rtt_millisecond=5,
                                 expert=_traced_expert(0), **kw))
    try:
        nh.start_replica({1: addr}, False, KVStateMachine, Config(
            shard_id=1, replica_id=1, election_rtt=10, heartbeat_rtt=2,
            compaction_overhead=5, device_resident=True))
        wait_leader({1: nh}, timeout=30)
        lifecycle.TRACER.reset()
        tracing.ROUNDS.reset()
        propose_retry(nh, nh.get_noop_session(1), b"a=1")
        nh.sync_read(1, b"a", timeout_s=10)
        deadline = time.time() + 10
        while time.time() < deadline and len(
                lifecycle.TRACER.completed()) < 2:
            time.sleep(0.05)
    finally:
        nh.close()
    by_kind = {tr["kind"]: tr for tr in lifecycle.TRACER.completed()}
    write, read = by_kind["proposal"], by_kind["read"]
    assert [s for s, _ in write["stamps"]] == GOLDEN_WRITE[logdb]
    assert [s for s, _ in read["stamps"]] == GOLDEN_READ
    assert all(len(stamp) == 2 for tr in (write, read)
               for stamp in tr["stamps"])
    # the causal link: the round that dispatched the write holds its key,
    # and the write's dispatch stamp falls inside that round
    rounds = [r for r in tracing.ROUNDS.rounds()
              if write["key"] in r["keys"]]
    assert len(rounds) == 1 and rounds[0]["engine"] == nh.id
    assert rounds[0]["props_staged"] == 1
    dispatched = dict(write["stamps"])["dispatch"]
    assert rounds[0]["t0_us"] <= dispatched <= rounds[0]["phases"][-1][1]
    assert any(r["reads_staged"] == 1 for r in tracing.ROUNDS.rounds())


def test_e2e_disabled_sampling_records_nothing():
    """trace_sample_every=0 turns every hook into a cheap no-op."""
    addrs = {1: "lcoff-1"}
    nh = NodeHost(NodeHostConfig(
        raft_address="lcoff-1", rtt_millisecond=5,
        expert=ExpertConfig(kernel_log_cap=256, kernel_capacity=8,
                            kernel_apply_batch=16,
                            kernel_compaction_overhead=16,
                            trace_sample_every=0)))
    try:
        cfg = Config(shard_id=1, replica_id=1, election_rtt=10,
                     heartbeat_rtt=2, compaction_overhead=5,
                     device_resident=True)
        nh.start_replica(addrs, False, KVStateMachine, cfg)
        assert not lifecycle.TRACER.enabled
        wait_leader({1: nh}, timeout=30)
        propose_retry(nh, nh.get_noop_session(1), b"off=1")
        assert lifecycle.TRACER.completed() == []
        assert lifecycle.TRACER.active_count() == 0
    finally:
        nh.close()

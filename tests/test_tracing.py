"""Tracing hooks: the engine round timer, its ring, and the profiler
spans (no-ops without a capture, named and nested under one)."""

import contextlib
import threading
import time
from types import SimpleNamespace

import pytest

from dragonboat_tpu import telemetry, tracing
from dragonboat_tpu.events import Metrics
from dragonboat_tpu.tracing import RoundTimer, annotate


class _Clock:
    """Hand-driven nanosecond clock: ``tick(us)`` advances it."""

    def __init__(self, start_us: int = 1_000):
        self.ns = start_us * 1000

    def tick(self, us: float) -> None:
        self.ns += int(us * 1000)

    def __call__(self) -> int:
        return self.ns


def make_round_timer(prefix="engine.test", engine="e1"):
    """Isolated timer: hand clocks, private registry and book."""
    wall, cpu = _Clock(), _Clock(0)
    reg = telemetry.Registry()
    book = tracing.RoundBook(ring_size=8)
    m = Metrics()
    rt = RoundTimer(m, prefix, engine=engine, registry=reg, book=book,
                    clock_ns=wall, cpu_clock_ns=cpu)
    return rt, wall, cpu, reg, book, m


#: one serial round: the time (us) spent in each phase, in order
SERIAL = (("stage", 30), ("upload", 50), ("fetch", 400), ("resolve", 70),
          ("save", 200), ("resolve", 10), ("finish", 40))


def run_round(rt, wall, cpu, schedule=SERIAL, cpu_us=100):
    rt.begin()
    for i, (phase, us) in enumerate(schedule):
        if i:                    # begin() opened ``stage``
            rt.enter(phase)
        wall.tick(us)
    cpu.tick(cpu_us)
    rt.commit(props_staged=2, keys=[64])


def test_round_timer_phases_total_wait_and_idle_passes():
    """Phases in order, each observed once a round (0 where not
    entered), ``total`` = stage..finish, ``wait`` spans two recorded
    rounds, an abandoned pass records nothing, ``ewma_us`` still fed."""
    rt, wall, cpu, reg, book, m = make_round_timer()
    run_round(rt, wall, cpu)
    wall.tick(25)
    with rt:                     # an idle step_all: nothing recorded
        wall.tick(5)
    wall.tick(70)
    run_round(rt, wall, cpu, schedule=(("stage", 10), ("upload", 90)))
    snap = reg.snapshot()

    def got(part, phase):
        return snap[f"engine_round_us.{part}{{phase={phase}}}"]

    for phase in tracing.ROUND_PHASES[1:] + ("total",):
        assert got("count", phase) == 2, phase
    assert got("count", "wait") == 1          # the first round has none
    assert got("sum", "wait") == pytest.approx(100.0)   # 25 + 5 + 70
    want = {"stage": 40, "upload": 140, "fetch": 400, "resolve": 80,
            "save": 200, "finish": 40}
    for phase, us in want.items():
        assert got("sum", phase) == pytest.approx(us), phase
    assert got("sum", "total") == pytest.approx(sum(want.values()))
    assert snap["engine_round_cpu_us.count"] == 2
    assert snap["engine_round_cpu_us.sum"] == pytest.approx(200.0)
    # 800 seeds the EWMA; then 0.9 * 800 + 0.1 * 100
    assert m.snapshot() == {"engine.test.ewma_us": 730}
    first, second = book.rounds()
    assert [p for p, _ in first["phases"]] == [
        "stage", "upload", "fetch", "resolve", "save", "resolve", "finish",
        "end"]
    assert [p for p, _ in second["phases"]] == [
        "wait", "stage", "upload", "end"]
    assert second["phases"][0][1] == first["phases"][-1][1]
    assert (first["engine"], first["seq"], second["seq"]) == ("e1", 1, 2)
    assert first["t0_us"] == 1_000 and first["cpu_us"] == 100
    assert first["props_staged"] == 2 and first["keys"] == [64]
    assert book.counts() == {"recorded": 2, "retained": 2, "overwritten": 0}


def test_round_timer_pipelined_order_and_chrome_row():
    """Depth 1 retires step N-1 before it uploads step N, and the fleet
    collection re-enters ``finish``: a phase's entries add up, and the
    record renders as one validator-clean row per engine."""
    from dragonboat_tpu.lifecycle import validate_chrome_trace

    rt, wall, cpu, reg, book, _m = make_round_timer(engine="host-a")
    pipelined = (("stage", 10), ("fetch", 300), ("resolve", 20),
                 ("finish", 30), ("upload", 60), ("finish", 15))
    run_round(rt, wall, cpu, schedule=pipelined)
    run_round(rt, wall, cpu, schedule=pipelined)
    snap = reg.snapshot()
    assert snap["engine_round_us.sum{phase=finish}"] == pytest.approx(90.0)
    assert snap["engine_round_us.sum{phase=save}"] == 0.0
    assert snap["engine_round_us.count{phase=save}"] == 2
    events = book.chrome_events()
    assert validate_chrome_trace({"traceEvents": events}) == len(events)
    assert {(e["pid"], e["tid"], e["cat"]) for e in events} == {
        ("engine", "host-a", "round")}
    assert [e["name"] for e in events[:6]] == [p for p, _ in pipelined]
    assert [e["dur"] for e in events[:6]] == [us for _, us in pipelined]
    assert events[6]["name"] == "wait" and events[6]["dur"] == 0
    # enter/commit outside a round are no-ops (a direct output pass)
    rt.enter("fetch")
    rt.commit()
    assert book.counts()["recorded"] == 2


def test_round_timer_reads_the_cpu_clock_at_the_phase_boundaries():
    """``engine_round_phase_cpu_us.sum{phase}``: the thread CPU time of
    each phase, a phase entered twice adding up, 0 where not entered, the
    six summing to what the unlabelled ``engine_round_cpu_us`` observes
    for the round (the same reads), in a round that was drawn; in a round
    that was not, the clock is read at the round's two ends alone, as
    ever.  (An engine's first rounds are all drawn; the draw itself is the
    next test's.)"""
    rt, wall, cpu, reg, book, _m = make_round_timer()
    reads = []
    counted = rt.cpu_clock_ns
    rt.cpu_clock_ns = lambda: reads.append(1) or counted()
    #: (phase, host us, CPU us) in a serial round's order
    serial = (("stage", 30, 20), ("upload", 50, 10), ("fetch", 400, 5),
              ("resolve", 70, 40), ("save", 200, 15), ("resolve", 10, 8),
              ("finish", 40, 2))
    short = (("stage", 10, 7), ("upload", 90, 3))
    for schedule in (serial, short):
        rt.begin()
        for i, (phase, us, cpu_us) in enumerate(schedule):
            if i:
                rt.enter(phase)
            wall.tick(us)
            cpu.tick(cpu_us)
        rt.commit()
        with rt:                 # an idle pass between: nothing of it kept
            wall.tick(3)
            cpu.tick(3)
    assert len(reads) == (1 + 6 + 1) + 1 + (1 + 1 + 1) + 1
    rt._mean_ns = 0              # rounds of no length: none is drawn
    run_round(rt, wall, cpu, cpu_us=40)
    assert len(reads) == 13 + 2
    snap = reg.snapshot()
    want = {"stage": 27, "upload": 13, "fetch": 5, "resolve": 48,
            "save": 15, "finish": 2}
    for phase, us in want.items():
        assert snap[f"engine_round_phase_cpu_us.sum{{phase={phase}}}"] \
            == pytest.approx(us), phase
    assert "engine_round_phase_cpu_us.sum{phase=wait}" not in snap
    assert "engine_round_phase_cpu_us.sum{phase=total}" not in snap
    # the whole round's family keeps its name, no label and its reading,
    # in every round
    assert snap["engine_round_cpu_us.count"] == 3
    assert snap["engine_round_cpu_us.sum"] == pytest.approx(
        sum(want.values()) + 40)
    assert not [k for k in snap if k.startswith("engine_round_cpu_us")
                and "{" in k]
    assert [r["cpu_us"] for r in book.rounds()] == [100, 10, 40]


def test_the_rounds_that_read_the_phases_are_drawn_evenly():
    """Which rounds read the CPU clock at their boundaries depends on the
    round's number and the engine's slow mean round, not on the round
    before: with every tenth round three and a half times as long as the
    others (the engine's collection) about a quarter of the rounds are
    read (mean round 10 ms of ``PHASE_CPU_EVERY_NS`` 40), of the long
    ones as of each other tenth, so ``finish`` reads its true share of
    the CPU time."""
    rt, wall, cpu, reg, _book, _m = make_round_timer()
    reads = []
    counted = rt.cpu_clock_ns
    rt.cpu_clock_ns = lambda: reads.append(1) or counted()
    drawn = []
    for i in range(4_500):
        long = i % 10 == 9
        before = len(reads)
        rt.begin()
        wall.tick(6_000)
        cpu.tick(3_000)
        rt.enter("finish")
        wall.tick(22_000 if long else 2_000)
        cpu.tick(17_000 if long else 1_000)
        rt.commit()
        drawn.append(len(reads) - before == 3)
        wall.tick(100)
    settled = drawn[500:]        # (the first rounds are all read)
    assert sum(settled) / len(settled) == pytest.approx(0.25, abs=0.02)
    for tenth in range(10):
        mine = settled[tenth::10]
        assert sum(mine) / len(mine) == pytest.approx(0.25, abs=0.04), tenth
    snap = reg.snapshot()
    stage = snap["engine_round_phase_cpu_us.sum{phase=stage}"]
    finish = snap["engine_round_phase_cpu_us.sum{phase=finish}"]
    truth = (9 * 1 + 17) / (10 * 3 + 9 * 1 + 17)
    assert finish / (stage + finish) == pytest.approx(truth, rel=0.03)
    assert rt._mean_ns == pytest.approx(10_000_000, rel=0.2)
    # an engine whose rounds are 40 ms or longer reads every one
    rt, wall, cpu, reg, _book, _m = make_round_timer()
    for _ in range(200):
        run_round(rt, wall, cpu, schedule=(("stage", 45_000),), cpu_us=10)
    assert reg.snapshot()["engine_round_phase_cpu_us.sum{phase=stage}"] \
        == pytest.approx(2_000)


def test_a_collected_timer_leaves_its_sums_in_the_gauges():
    """The sums are the timers' own (no lock or histogram a round); the
    callback gauges add up every live timer's and keep what a collected
    one had summed, so a window's growth never reads negative."""
    import gc

    rt, wall, cpu, reg, book, m = make_round_timer()
    other = RoundTimer(m, "engine.other", engine="e2", registry=reg,
                       book=book, clock_ns=wall, cpu_clock_ns=cpu)
    for timer in (rt, other):
        timer.begin()
        with timer.part("stage.reset"):
            wall.tick(4)
        timer.mark("responses_out")
        timer.commit()
    key = "engine_round_part_us.sum{part=stage.reset}"
    assert reg.snapshot()[key] == pytest.approx(8)
    assert reg.kind_of("engine_round_part_us.sum") == "gauge"
    del rt, timer
    gc.collect()
    snap = reg.snapshot()
    assert snap[key] == pytest.approx(8)
    assert snap["engine_round_mark_us.count{mark=responses_out}"] == 2
    other.begin()
    with other.part("stage.reset"):
        wall.tick(5)
    other.commit()
    assert reg.snapshot()[key] == pytest.approx(13)


def test_round_timer_parts_stay_inside_their_phase():
    """``part`` (entered once or twice a round) and ``add`` (what the
    caller summed itself) feed ``engine_round_part_us.sum{part}``, host
    time only (no part has a CPU family), summed over the committed
    rounds (which ``engine_round_us{phase=total}`` counts); the phases'
    own histograms are untouched; the record carries all seven, 0 where
    not entered."""
    rt, wall, cpu, reg, book, _m = make_round_timer()
    rt.begin()
    with rt.part("stage.reset"):
        wall.tick(4)
    wall.tick(6)
    rt.enter("upload")
    wall.tick(20)
    with rt.part("upload.release"):
        wall.tick(9)
        cpu.tick(1)
    rt.enter("resolve")
    with rt.part("resolve.send"):
        wall.tick(5)
    rt.enter("save")
    wall.tick(50)
    rt.enter("resolve")
    with rt.part("resolve.send"):
        wall.tick(7)
    rt.enter("finish")
    wall.tick(30)
    cpu.tick(12)
    rt.add("finish.apply", 11_000)
    rt.add("finish.ack", 8_000)
    rt.add("finish.ack", 6_000)
    rt.commit()
    run_round(rt, wall, cpu)     # a round that entered no part
    snap = reg.snapshot()
    want = {"stage.reset": 4, "stage.tick": 0, "upload.release": 9,
            "upload.applied": 0, "resolve.send": 12, "finish.apply": 11,
            "finish.ack": 14, "finish.collect": 0}
    assert set(want) == set(tracing.ROUND_PARTS)
    for part, us in want.items():
        assert snap[f"engine_round_part_us.sum{{part={part}}}"] \
            == pytest.approx(us), part
    assert snap["engine_round_us.count{phase=total}"] == 2
    assert not [k for k in snap if k.startswith("engine_round_part_us.count")]
    assert not [k for k in snap if k.startswith("engine_round_part_cpu_us")]
    # a part's time is inside its phase's, which reads what it read
    # without parts: 10 + 30, 29 + 50, 12 + 80, 30 + 40
    phases = {"stage": 40, "upload": 79, "resolve": 92, "finish": 70}
    for phase, us in phases.items():
        assert snap[f"engine_round_us.sum{{phase={phase}}}"] \
            == pytest.approx(us), phase
        inside = sum(v for p, v in want.items() if p.startswith(phase))
        assert inside <= us
    first, second = book.rounds()
    assert first["parts"] == want
    assert second["parts"] == dict.fromkeys(want, 0)
    assert first["marks"] == second["marks"] == {}


def test_round_timer_marks_only_the_rounds_that_sent():
    """``engine_round_mark_us{mark}`` holds the time from a round's start
    to ``mark``: its count is the rounds that made the mark, and the
    record and ``/trace`` carry it (an instant event in the engine's row,
    in clock order)."""
    from dragonboat_tpu.lifecycle import validate_chrome_trace

    rt, wall, cpu, reg, book, _m = make_round_timer(engine="host-a")
    rt.begin()
    wall.tick(30)
    rt.enter("resolve")
    wall.tick(5)
    rt.mark("replicates_out")
    wall.tick(1)
    rt.enter("save")
    wall.tick(20)
    rt.enter("resolve")
    wall.tick(2)
    rt.mark("responses_out")
    wall.tick(1)
    rt.enter("finish")
    wall.tick(10)
    rt.commit()
    wall.tick(4)
    rt.begin()                   # a follower's round: nothing to replicate
    wall.tick(12)
    rt.mark("responses_out")
    wall.tick(1)
    rt.commit()
    run_round(rt, wall, cpu)     # and one that sent nothing at all
    snap = reg.snapshot()
    assert snap["engine_round_mark_us.count{mark=replicates_out}"] == 1
    assert snap["engine_round_mark_us.sum{mark=replicates_out}"] \
        == pytest.approx(35)
    assert snap["engine_round_mark_us.count{mark=responses_out}"] == 2
    assert snap["engine_round_mark_us.sum{mark=responses_out}"] \
        == pytest.approx(58 + 12)
    assert snap["engine_round_us.count{phase=total}"] == 3
    recs = book.rounds()
    assert [r["marks"] for r in recs] == [
        {"replicates_out": 35, "responses_out": 58}, {"responses_out": 12},
        {}]
    events = book.chrome_events()
    assert validate_chrome_trace({"traceEvents": events}) == len(events)
    assert {(e["pid"], e["tid"]) for e in events} == {("engine", "host-a")}
    instants = [(e["name"], e["ts"]) for e in events if e["ph"] == "i"]
    t0, t1 = recs[0]["t0_us"], recs[1]["t0_us"]
    assert instants == [("replicates_out", t0 + 35),
                        ("responses_out", t0 + 58),
                        ("responses_out", t1 + 12)]
    assert [e["name"] for e in events[:7]] == [
        "stage", "resolve", "replicates_out", "save", "resolve",
        "responses_out", "finish"]
    spans = [e for e in events if e["ph"] == "X"]
    assert spans[0]["args"]["parts"] == recs[0]["parts"]
    assert spans[0]["args"]["marks"] == recs[0]["marks"]


@pytest.mark.parametrize("how", ["outside", "abandoned", "raised"])
def test_parts_and_marks_of_no_round_are_dropped(how):
    """``part``, ``add`` and ``mark`` outside an open round do nothing,
    and those of a pass that is abandoned, or raises, are recorded
    nowhere: the next round starts from nothing."""
    rt, wall, cpu, reg, book, _m = make_round_timer()

    def feed():
        with rt.part("stage.reset"):
            wall.tick(5)
        rt.add("finish.ack", 7_000)
        rt.mark("replicates_out")

    if how == "outside":
        feed()
    elif how == "abandoned":
        with rt:
            feed()
    else:
        with pytest.raises(ZeroDivisionError):
            with rt:
                rt.enter("finish")
                cpu.tick(9)
                feed()
                1 / 0
    snap = reg.snapshot()
    assert not [k for k, v in snap.items() if v
                and k.startswith("engine_round_")]
    assert book.rounds() == []
    run_round(rt, wall, cpu)
    snap = reg.snapshot()
    assert snap["engine_round_us.count{phase=total}"] == 1
    assert snap["engine_round_part_us.sum{part=stage.reset}"] == 0
    assert snap["engine_round_part_us.sum{part=finish.ack}"] == 0
    assert snap["engine_round_phase_cpu_us.sum{phase=finish}"] \
        == pytest.approx(100)
    assert snap.get("engine_round_mark_us.count{mark=replicates_out}", 0) == 0
    (rec,) = book.rounds()
    assert rec["marks"] == {} and not any(rec["parts"].values())


def test_the_process_cpu_clock_is_a_callback_gauge():
    """``process_cpu_us``: the whole process's CPU time, read when a
    snapshot is taken (nothing on the hot path feeds it)."""
    a = telemetry.GLOBAL.snapshot()["process_cpu_us"]
    sum(i * i for i in range(200_000))
    b = telemetry.GLOBAL.snapshot()["process_cpu_us"]
    assert isinstance(a, int) and b > a >= 0
    assert telemetry.GLOBAL.kind_of("process_cpu_us") == "gauge"


def test_round_timer_sets_the_thread_phase():
    rt, wall, cpu, *_ = make_round_timer()
    assert tracing.current_phase() == "none"
    rt.begin()
    assert tracing.current_phase() == "stage"
    rt.enter("fetch")
    assert tracing.current_phase() == "fetch"
    rt.commit()
    assert tracing.current_phase() == "none"
    with pytest.raises(ZeroDivisionError):
        with rt:                 # a pass that raises leaves nothing open
            rt.enter("save")
            1 / 0
    assert tracing.current_phase() == "none"
    run_round(rt, wall, cpu)
    assert [p for p, _ in rt._book.rounds()[-1]["phases"]][1:] == [
        p for p, _ in SERIAL] + ["end"]


def test_a_pass_that_raises_in_step_all_leaves_no_round_open():
    """The host's worker logs an engine exception and loops on: the
    round it interrupted is dropped, not left open with a stale phase
    for the compile listener to read."""
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.engine.kernel_engine import KernelEngine

    eng = KernelEngine(KP.KernelParams(), capacity=4, send_message=None,
                       label="raising-host")
    eng.nodes = {1: object()}

    def boom():
        assert tracing.current_phase() == "stage"
        raise RuntimeError("injection failed")

    eng._flush_injections = boom
    eng.tick_round()            # a pass with nothing to do leaves at once
    with pytest.raises(RuntimeError, match="injection failed"):
        eng.step_all()
    assert tracing.current_phase() == "none"
    assert eng._round._t0 is None and not eng._round._marks
    assert not [r for r in tracing.ROUNDS.rounds()
                if r["engine"] == "raising-host"]
    eng.nodes = {}
    eng.close()


def test_trace_ring_drain_and_overwritten():
    """``overwritten`` counts only what no reader had seen: a snapshot or
    a drain marks everything retained as read."""
    ring = tracing.TraceRing(2)
    for i in range(3):
        ring.append(i)
    assert ring.overwritten == 1 and ring.snapshot() == [1, 2]
    ring.append(3)               # pushes out 1, which was read
    assert ring.overwritten == 1
    assert ring.drain() == [2, 3] and len(ring) == 0
    for i in range(4, 8):
        ring.append(i)           # 4 and 5 go unread
    assert ring.overwritten == 3 and ring.drain() == [6, 7]
    with pytest.raises(ValueError):
        tracing.TraceRing(0)


def test_an_idle_step_all_records_no_round():
    """An engine with nothing to do returns False from ``step_all`` and
    leaves no trace of a round: no record, no observation, and the
    thread's phase back at ``none``."""
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.engine.kernel_engine import KernelEngine

    eng = KernelEngine(KP.KernelParams(), capacity=4, send_message=None,
                       label="idle-host")
    key = "engine_round_us.count{phase=total}"
    before = telemetry.GLOBAL.snapshot().get(key, 0)
    for _ in range(3):
        assert eng.step_all() is False
    assert telemetry.GLOBAL.snapshot().get(key, 0) == before
    assert not [r for r in tracing.ROUNDS.rounds()
                if r["engine"] == "idle-host"]
    assert tracing.current_phase() == "none"
    assert "engine.kernel_step.ewma_us" not in eng.events.metrics.snapshot()
    eng.close()


def test_staging_counts_admission_at_the_slot_boundary():
    """8 proposals offered to a ``proposal_cap`` of 4: 4 staged, 4 put
    back, and the row's 4 slots offered once."""
    from dragonboat_tpu import raftpb as pb
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.engine.kernel_engine import KernelEngine

    eng = KernelEngine(KP.KernelParams(proposal_cap=4), capacity=4,
                       send_message=None)
    node = SimpleNamespace(lane=0, mu=threading.Lock(), _staged_props=[],
                           incoming_proposals=[], config_change_entry=None)
    eng._slot_cursor = {}
    keys = ("engine_props_staged", "engine_props_deferred",
            "engine_prop_slots_offered")
    before = telemetry.GLOBAL.snapshot()
    props = [pb.Entry(cmd=b"k=v") for _ in range(8)]
    eng._stage_props(0, node, eng._input_buf, None, props)
    after = telemetry.GLOBAL.snapshot()
    assert [after[k] - before[k] for k in keys] == [4, 4, 4]
    assert len(node._staged_props) == 4
    assert node.incoming_proposals == props[4:]
    assert (eng._props_staged, eng._props_deferred) == (4, 4)
    # a second pass onto the full row stages nothing and offers nothing
    eng._stage_props(0, node, eng._input_buf, None, props[4:6])
    last = telemetry.GLOBAL.snapshot()
    assert [last[k] - after[k] for k in keys] == [0, 2, 0]
    eng.close()


def test_annotate_is_safe_without_capture():
    with annotate("noop-span"):
        x = 1 + 1
    assert x == 2


def test_annotate_is_nullcontext_without_capture(monkeypatch):
    """With no active capture, annotate must return a plain
    nullcontext — no jax import, no TraceAnnotation object (the hot
    path relies on this being free)."""
    monkeypatch.setattr(tracing, "_active_trace_dir", None)
    cm = annotate("should-be-free")
    assert isinstance(cm, contextlib.nullcontext)


def test_monotonic_us_is_monotone():
    a = tracing.monotonic_us()
    b = tracing.monotonic_us()
    assert isinstance(a, int) and b >= a >= 0


class _FakeAnnotation:
    def __init__(self, log, name, meta):
        self.log, self.name, self.meta = log, name, meta

    def __enter__(self):
        self.log.append(("enter", self.name, self.meta,
                         threading.get_ident()))
        return self

    def set_metadata(self, **meta):
        self.meta = {**self.meta, **meta}      # the exit's entry shows it

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.meta,
                         threading.get_ident()))
        return False


class _FakeProfiler:
    """Stands in for jax.profiler: records start/stop calls, the options
    a start was given, and every annotation's enter and exit."""

    def __init__(self):
        self.calls = []
        self.options = None
        self.log = []

    def ProfileOptions(self):                      # noqa: N802 (jax's name)
        return SimpleNamespace(python_tracer_level=None,
                               host_tracer_level=None)

    def start_trace(self, d, profiler_options=None):
        self.calls.append(("start", d))
        self.options = profiler_options

    def stop_trace(self):
        self.calls.append(("stop", None))

    def TraceAnnotation(self, name, **meta):       # noqa: N802
        return _FakeAnnotation(self.log, name, meta)


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax

    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    monkeypatch.setattr(tracing, "_active_trace_dir", None)
    monkeypatch.setattr(tracing, "_env_armed", False)
    yield fake
    # never leak an armed capture into the next test
    tracing._active_trace_dir = None
    tracing._env_armed = False


def test_stop_env_trace_ignores_user_capture(fake_profiler, tmp_path):
    """A capture the user started with start_trace is NOT env-armed:
    stop_env_trace must leave it running (the user owns its lifetime)."""
    tracing.start_trace(str(tmp_path))
    assert tracing.stop_env_trace() is None
    assert tracing._active_trace_dir == str(tmp_path)
    assert tracing.stop_trace() == str(tmp_path)


def test_engine_close_stops_env_armed_trace(fake_profiler, tmp_path,
                                            monkeypatch):
    """Regression (satellite): an env-armed capture must be stopped and
    flushed by engine close(), not left to atexit ordering."""
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.engine.kernel_engine import KernelEngine

    d = str(tmp_path / "cap")
    monkeypatch.setenv("DRAGONBOAT_TPU_TRACE_DIR", d)
    eng = KernelEngine(KP.KernelParams(), capacity=4, send_message=None)
    assert tracing._active_trace_dir == d
    assert tracing._env_armed
    eng.close()
    assert tracing._active_trace_dir is None
    assert not tracing._env_armed
    assert ("start", d) in fake_profiler.calls
    assert ("stop", None) in fake_profiler.calls
    # idempotent: a second close must not double-stop
    eng.close()
    assert fake_profiler.calls.count(("stop", None)) == 1


def test_double_start_trace_raises(tmp_path, monkeypatch):
    """A second start_trace while one is active must raise a clear
    error instead of silently clobbering _active_trace_dir (which would
    make stop_trace report the wrong capture directory)."""
    monkeypatch.setattr(tracing, "_active_trace_dir", str(tmp_path / "a"))
    with pytest.raises(RuntimeError, match="already active"):
        tracing.start_trace(str(tmp_path / "b"))


def test_start_trace_passes_options_and_marks_the_clock(fake_profiler,
                                                        tmp_path):
    """``start_trace`` hands the two tracer levels to the profiler, and
    both ends of the capture carry one ``tracing.clock_sync`` annotation
    whose metadata is this module's clock."""
    before = tracing.monotonic_us()
    tracing.start_trace(str(tmp_path), python_tracer_level=0,
                        host_tracer_level=2)
    assert fake_profiler.options.python_tracer_level == 0
    assert fake_profiler.options.host_tracer_level == 2
    assert tracing.stop_trace() == str(tmp_path)
    marks = [(op, meta["monotonic_us"]) for op, name, meta, _ in
             fake_profiler.log if name == tracing.CLOCK_SYNC]
    assert [op for op, _ in marks] == ["enter", "exit", "enter", "exit"]
    assert before <= marks[0][1] <= marks[2][1] <= tracing.monotonic_us()
    # the stop's mark is written while the profiler still runs
    assert fake_profiler.calls == [("start", str(tmp_path)), ("stop", None)]
    # no options asked: the profiler's default start
    tracing.start_trace(str(tmp_path))
    assert fake_profiler.options is None
    tracing.stop_trace()


def test_a_capture_armed_by_hand_gets_one_clock_mark(fake_profiler,
                                                      tmp_path):
    """The benchmark harness starts the profiler itself and sets
    ``_active_trace_dir`` by hand: the first annotation the round timer
    writes into such a capture is preceded by ONE ``tracing.clock_sync``
    mark (whichever engine's timer comes first), a later capture gets its
    own, and ``start_trace``'s two marks stay two."""
    rt, wall, cpu, *_ = make_round_timer(engine="h")
    other, *_ = make_round_timer(engine="g")

    def names():
        return [(op, name) for op, name, _meta, _t in fake_profiler.log]

    run_round(rt, wall, cpu)             # no capture: nothing written
    assert names() == []
    for d in ("a", "b", "b"):            # (the same directory twice, too)
        del fake_profiler.log[:]
        before = tracing.monotonic_us()
        tracing._active_trace_dir = str(tmp_path / d)
        run_round(rt, wall, cpu, schedule=(("stage", 10), ("upload", 20)))
        with other:
            pass
        tracing._active_trace_dir = None
        with rt:                         # the timer sees the capture end
            pass
        log = names()
        sync = ("enter", tracing.CLOCK_SYNC), ("exit", tracing.CLOCK_SYNC)
        assert tuple(log[:2]) == sync
        assert log[2] == ("enter", "kernel_engine.stage")
        assert log.count(sync[0]) == 1
        meta = fake_profiler.log[0][2]
        assert before <= meta["monotonic_us"] <= tracing.monotonic_us()
    # a capture start_trace armed holds its own two marks and no third
    del fake_profiler.log[:]
    tracing.start_trace(str(tmp_path / "c"))
    run_round(rt, wall, cpu)
    tracing.stop_trace()
    assert names().count(("enter", tracing.CLOCK_SYNC)) == 2
    assert names()[:3] == [("enter", tracing.CLOCK_SYNC),
                           ("exit", tracing.CLOCK_SYNC),
                           ("enter", "kernel_engine.stage")]


def test_three_engine_threads_write_one_mark_ahead_of_every_annotation(
        fake_profiler, tmp_path, monkeypatch):
    """Up to three engine threads meet a capture armed by hand at once:
    one of them writes the mark, and the others wait for it before they
    write their own first annotation (a mark that is slow to write shows
    the order)."""
    sync = tracing._clock_sync

    def slow_sync():
        time.sleep(0.05)
        sync()

    monkeypatch.setattr(tracing, "_clock_sync", slow_sync)
    timers = [make_round_timer(engine=f"h{i}")[0] for i in range(3)]
    gate = threading.Barrier(3)

    def one_pass(rt):
        gate.wait()
        with rt:
            pass

    tracing._active_trace_dir = str(tmp_path / "raced")
    threads = [threading.Thread(target=one_pass, args=(rt,))
               for rt in timers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tracing._active_trace_dir = None
    log = [(op, name) for op, name, _meta, _t in fake_profiler.log]
    assert log[:2] == [("enter", tracing.CLOCK_SYNC),
                       ("exit", tracing.CLOCK_SYNC)]
    assert log.count(("enter", tracing.CLOCK_SYNC)) == 1
    assert log.count(("enter", "kernel_engine.stage")) == 3


def one_shard_host(address):
    """A NodeHost serving one single-member device-resident shard."""
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from test_nodehost import KVStateMachine

    nh = NodeHost(NodeHostConfig(
        raft_address=address, rtt_millisecond=5,
        expert=ExpertConfig(kernel_log_cap=256, kernel_capacity=8,
                            kernel_apply_batch=16,
                            kernel_compaction_overhead=16)))
    try:
        nh.start_replica({1: address}, False, KVStateMachine, Config(
            shard_id=1, replica_id=1, election_rtt=10, heartbeat_rtt=2,
            compaction_overhead=5, device_resident=True))
    except BaseException:
        nh.close()
        raise
    return nh


def test_start_replica_times_its_phases_and_the_engine_its_admission():
    """One ``start_replica``: ``open`` + ``build`` + ``stage`` observed
    once each and summing to ``total``; the engine's own two instruments
    (the caller's wait for the engine lock, inside ``stage``; one
    injection batch on the engine thread) are no phases of the call."""
    from test_nodehost import wait_leader

    def snap():
        return {k: v for k, v in telemetry.GLOBAL.snapshot().items()
                if k.startswith(("nodehost_start_replica_us.sum",
                                 "nodehost_start_replica_us.count",
                                 "engine_add_shard_lock_us.",
                                 "engine_inject_flush_us."))}

    before = snap()
    nh = one_shard_host("phases-1")
    try:
        wait_leader({1: nh}, timeout=30)      # the lane was injected
    finally:
        nh.close()
    grew = {k: v - before.get(k, 0) for k, v in snap().items()}
    phases = {k.split("=")[1].rstrip("}") for k in grew
              if k.startswith("nodehost_start_replica_us")}
    assert phases == {"open", "build", "stage", "total"}

    def call(part, phase):
        return grew[f"nodehost_start_replica_us.{part}{{phase={phase}}}"]

    assert all(call("count", p) == 1 for p in phases)
    assert call("sum", "total") == pytest.approx(
        sum(call("sum", p) for p in ("open", "build", "stage")))
    assert grew["engine_add_shard_lock_us.count"] == 1
    assert grew["engine_add_shard_lock_us.sum"] <= call("sum", "stage")
    assert grew["engine_inject_flush_us.count"] == 1
    assert grew["engine_inject_flush_us.sum"] > 0


def test_round_timer_marks_idle_passes_and_nests_on_a_raise(fake_profiler,
                                                            tmp_path):
    """In a capture a pass opens ``kernel_engine.stage`` before the
    engine knows whether it is a round: the one of a pass that is none
    leaves with ``idle=1``, a round's does not; and a phase annotation
    is closed before the older annotation around it, raise or not."""
    rt, wall, cpu, *_ = make_round_timer(engine="h")
    tracing.start_trace(str(tmp_path))
    with rt:
        wall.tick(5)
    run_round(rt, wall, cpu, schedule=(("stage", 10), ("upload", 20)))
    with pytest.raises(ZeroDivisionError):
        with rt:
            with rt.within("kernel_engine.process_outputs"):
                rt.enter("fetch")
                1 / 0
    tracing.stop_trace()
    log = [(op, name.removeprefix("kernel_engine."), meta)
           for op, name, meta, _ in fake_profiler.log
           if name.startswith("kernel_engine.")]
    h = {"engine": "h"}
    assert log == [
        ("enter", "stage", h), ("exit", "stage", {**h, "idle": 1}),
        ("enter", "stage", h), ("exit", "stage", h),
        ("enter", "upload", h), ("exit", "upload", h),
        ("enter", "stage", h), ("exit", "stage", h),
        ("enter", "process_outputs", {}),
        ("enter", "fetch", h), ("exit", "fetch", h),
        ("exit", "process_outputs", {})]


def test_round_annotations_names_and_nesting(fake_profiler, tmp_path):
    """Under a capture a served round writes ``kernel_engine.stage`` at
    the top level, ``upload`` inside the unchanged ``kernel_engine.step``
    and ``fetch`` / ``resolve`` / ``save`` / ``finish`` inside the
    unchanged ``kernel_engine.process_outputs``; the phase annotations
    carry the engine's label, the two old ones no metadata."""
    from test_kernel_engine import propose_retry
    from test_nodehost import wait_leader

    nh = one_shard_host("ann-1")
    try:
        wait_leader({1: nh}, timeout=30)
        tracing.start_trace(str(tmp_path))
        propose_retry(nh, nh.get_noop_session(1), b"a=1")
        tracing.stop_trace()
    finally:
        nh.close()
    log = [ev for ev in fake_profiler.log
           if ev[1].startswith("kernel_engine.")]
    # the capture may have been armed in the middle of a round: that
    # round's phases have no parent annotation, so start at the next one
    first = next(i for i, ev in enumerate(log)
                 if ev[:2] == ("enter", "kernel_engine.stage"))
    log = log[first:]
    engine_thread = log[0][3]
    assert all(ev[3] == engine_thread for ev in log)
    parent_of = {"stage": None, "upload": "step", "fetch": "process_outputs",
                 "resolve": "process_outputs", "save": "process_outputs",
                 "finish": ("process_outputs", None)}
    stack, seen, inside = [], set(), 0
    for op, name, meta, _thread in log:
        short = name.removeprefix("kernel_engine.")
        if op == "exit":
            assert stack and stack.pop() == short, (name, stack)
            if short == "stage" and meta.get("idle"):
                # a pass that was no round: marked, and nothing in it
                assert meta == {"engine": nh.id, "idle": 1}
                assert inside == 0
            continue
        inside = 0 if short == "stage" else inside + 1
        if short in ("step", "process_outputs"):
            assert not stack and meta == {}
        else:
            assert meta == {"engine": nh.id}
            want = parent_of[short]
            above = stack[-1] if stack else None
            assert above == want or (
                isinstance(want, tuple) and above in want), (name, stack)
        stack.append(short)
        seen.add(short)
    assert seen >= {"stage", "step", "upload", "process_outputs", "fetch",
                    "resolve", "save", "finish"}
    # the capture and the registry agree on what a round is: every stage
    # annotation that is not marked idle is followed by its round's step
    stages = [i for i, ev in enumerate(log)
              if ev[:2] == ("exit", "kernel_engine.stage")]
    for i in stages[:-1]:        # the last round may outlast the capture
        follows = log[i + 1][:2]
        if log[i][2].get("idle"):
            assert follows == ("enter", "kernel_engine.stage")
        else:
            assert follows in (("enter", "kernel_engine.step"),
                               ("enter", "kernel_engine.process_outputs"))

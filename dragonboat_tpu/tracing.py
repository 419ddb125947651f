"""Tracing / profiling hooks.

The reference has no tracer (SURVEY §5 flags this as a gap to fill, not a
port): its observability is module loggers + Prometheus counters.  The
TPU build adds a real trace path on top of the same metrics registry:

- ``start_trace(dir)`` / ``stop_trace()`` — JAX profiler capture (XLA
  device traces, host Python, HLO cost attribution) viewable in
  TensorBoard / Perfetto.  Both ends emit one ``tracing.clock_sync``
  annotation whose ``monotonic_us`` metadata is this module's clock at
  that instant, so host-clock records (lifecycle spans, round records)
  can be laid on the capture's time base;
- ``annotate(name, **meta)`` — named span visible inside the device
  trace (``jax.profiler.TraceAnnotation``), used around the kernel
  engine's step phases;
- ``RoundTimer`` — the engine round, phase by phase (``ROUND_PHASES``),
  on the host clock and the engine thread's CPU clock:
  ``engine_round_us{phase=...}`` / ``engine_round_cpu_us`` histograms in
  ``telemetry.GLOBAL``; the CPU time by phase
  (``engine_round_phase_cpu_us.sum{phase}``), the parts of its largest
  phases (``ROUND_PARTS``: ``engine_round_part_us.sum{part}``) and the
  moments a round's messages leave (``ROUND_MARKS``:
  ``engine_round_mark_us.sum|count{mark}``) as plain sums on the timer,
  published by callback gauges when a snapshot is taken; a
  ``kernel_engine.<phase>`` annotation per phase while a capture is
  armed, preceded by one ``tracing.clock_sync`` mark however the capture
  was armed; a bounded ring of round records for ``/trace``, and the
  ``<prefix>.ewma_us`` gauge the load controller reads.  Always on (the
  profiler itself is opt-in: capture costs memory);
- the callback gauge ``process_cpu_us``: the whole process's CPU time,
  read when a snapshot is taken, beside the engine threads' own.

Environment: ``DRAGONBOAT_TPU_TRACE_DIR`` arms profiler capture at import
of the engine, for drive-by profiling without code changes.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import weakref
from collections import deque

from dragonboat_tpu import telemetry

_active_trace_dir: str | None = None
# the capture directory that holds a ``tracing.clock_sync`` mark already:
# ``start_trace`` writes one, and the round timer writes one ahead of its
# first annotation into a capture that was armed by hand (the benchmark
# harness sets ``_active_trace_dir`` itself)
_synced_dir: str | None = None
_sync_mu = threading.Lock()     # guards _synced_dir and the mark it stands for
# set while the ACTIVE capture was armed by DRAGONBOAT_TPU_TRACE_DIR
# (maybe_start_from_env) rather than an explicit start_trace call —
# engine close() stops env-armed captures, never user-started ones
_env_armed = False


def monotonic_us() -> int:
    """Monotonic microsecond clock for lifecycle stage stamps.

    Lives HERE (outside the determinism lint scope) so lifecycle.py can
    receive it by injection: the tracer module itself never names a wall
    clock, tests inject a deterministic counter, and the lint keeps the
    replay-path modules honest."""
    return time.monotonic_ns() // 1000


CLOCK_SYNC = "tracing.clock_sync"


def _clock_sync() -> None:
    """One instant of this module's clock, written into the capture."""
    with annotate(CLOCK_SYNC, monotonic_us=monotonic_us()):
        pass


def _sync_capture(trace_dir: str | None) -> None:
    """Write the capture's one ``tracing.clock_sync`` mark unless
    ``trace_dir`` holds it already (None: the capture ended, so a later
    one into the same directory gets its own).  Up to three engine
    threads come here with their first annotation; the lock is held over
    the mark, so none writes its annotation ahead of it.  Taken once a
    capture by each thread that asks, never in a round without one."""
    global _synced_dir
    with _sync_mu:
        if _synced_dir != trace_dir:
            if trace_dir is not None:
                _clock_sync()
            # set after the mark: a thread that reads it without the lock
            # and finds the capture synced finds the mark written
            _synced_dir = trace_dir


def start_trace(trace_dir: str, python_tracer_level: int | None = None,
                host_tracer_level: int | None = None) -> None:
    """Begin a JAX profiler capture into ``trace_dir``.

    The two tracer levels are ``jax.profiler.ProfileOptions`` fields (a
    process with ~150 host threads wants ``python_tracer_level=0``);
    None leaves the profiler's default.

    Raises ``RuntimeError`` when a capture is already active: the JAX
    profiler is a process singleton, and silently overwriting
    ``_active_trace_dir`` would make ``stop_trace`` report the second
    dir while the capture file lands in the first."""
    global _active_trace_dir
    if _active_trace_dir is not None:
        raise RuntimeError(
            f"a trace is already active in {_active_trace_dir!r}; call "
            "stop_trace() before starting another capture")
    import jax

    if python_tracer_level is None and host_tracer_level is None:
        jax.profiler.start_trace(trace_dir)
    else:
        options = jax.profiler.ProfileOptions()
        if python_tracer_level is not None:
            options.python_tracer_level = python_tracer_level
        if host_tracer_level is not None:
            options.host_tracer_level = host_tracer_level
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    _active_trace_dir = trace_dir
    _sync_capture(trace_dir)


def stop_trace() -> str | None:
    """End the capture; returns the trace dir (None if none active)."""
    global _active_trace_dir, _env_armed
    if _active_trace_dir is None:
        return None
    import jax

    _clock_sync()
    jax.profiler.stop_trace()
    d, _active_trace_dir = _active_trace_dir, None
    _env_armed = False
    _sync_capture(None)
    return d


def stop_env_trace() -> str | None:
    """Stop the capture ONLY when it was armed by the environment
    (``DRAGONBOAT_TPU_TRACE_DIR``); returns the flushed dir, or None.

    Engine ``close()`` calls this: JAX only serializes a capture on
    stop, so an env-armed trace that survived to interpreter shutdown
    depended on atexit LIFO ordering to flush at all — a host that is
    closed deliberately should flush its capture right there, while the
    backend is unambiguously alive.  A capture the USER started with
    ``start_trace`` is left alone (they own its lifetime)."""
    if not _env_armed:
        return None
    return stop_trace()


_env_hook_registered = False


def _atexit_stop() -> None:
    """atexit wrapper: an env-armed capture may already have been
    stopped by hand, and interpreter-shutdown stops must never mask the
    real exit path with a profiler error."""
    try:
        stop_trace()
    except Exception:
        pass


def maybe_start_from_env() -> bool:
    """Arm capture when DRAGONBOAT_TPU_TRACE_DIR is set (idempotent).
    JAX only serializes the capture on stop, so an env-armed trace
    registers an atexit stop — otherwise the dir would stay empty.

    Ordering: atexit hooks run LIFO, so the stop hook must be
    registered AFTER the engine/JAX import chain has registered its own
    teardown (backend shutdown) — i.e. here, after ``start_trace`` has
    imported jax — or the profiler would try to serialize the capture
    into an already-torn-down backend.  The hook is registered exactly
    once per process."""
    global _env_hook_registered, _env_armed
    d = os.environ.get("DRAGONBOAT_TPU_TRACE_DIR")
    if d and _active_trace_dir is None:
        import atexit

        start_trace(d)          # imports jax; its atexit hooks exist now
        _env_armed = True
        if not _env_hook_registered:
            _env_hook_registered = True
            atexit.register(_atexit_stop)
        return True
    return False


def annotate(name: str, **meta):
    """Named span in the device trace (``meta`` becomes the event's
    metadata); near-zero cost when no capture is active (a module-flag
    check, no jax import or span object)."""
    if _active_trace_dir is None:
        return contextlib.nullcontext()
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **meta)
    except Exception:
        return contextlib.nullcontext()


class TraceRing:
    """Bounded ring of completed records that counts what it loses:
    ``overwritten`` is the number of records pushed out before any
    reader (``snapshot`` or ``drain``) had seen them.  Not locked — the
    owner guards it with its own mutex."""

    __slots__ = ("_q", "appended", "_seen", "overwritten")

    def __init__(self, size: int) -> None:
        if size <= 0:
            raise ValueError(f"ring_size must be positive, got {size}")
        self._q: deque = deque(maxlen=size)
        self.appended = 0       # records ever appended
        self._seen = 0          # ... of which a reader has seen this many
        self.overwritten = 0

    def append(self, rec) -> None:
        q = self._q
        if len(q) == q.maxlen and self.appended - len(q) >= self._seen:
            self.overwritten += 1
        q.append(rec)
        self.appended += 1

    def snapshot(self) -> list:
        """The retained records, oldest first; they stay in the ring."""
        self._seen = self.appended
        return list(self._q)

    def drain(self) -> list:
        """Return the retained records, oldest first, and clear."""
        out = self.snapshot()
        self._q.clear()
        return out

    def clear(self) -> None:
        self._q.clear()
        self.appended = self._seen = self.overwritten = 0

    def __len__(self) -> int:
        return len(self._q)


#: phases of one engine round in the serial engine's order
#: (kernel_engine.py names the boundaries).  ``wait`` is the time between
#: two recorded rounds; the other six are contiguous and sum to ``total``
ROUND_PHASES = ("wait", "stage", "upload", "fetch", "resolve", "save",
                "finish")
ROUND_TOTAL = "total"
#: what the largest phases are made of, ``<phase>.<part>``: a part's time
#: stays inside its phase (a phase's self time is its time less its
#: parts).  Each has a reader under ``benchmark/layer_metrics/``; a part
#: nobody reads is not recorded.  kernel_engine.py names the extents
ROUND_PARTS = ("stage.reset", "stage.tick", "upload.release",
               "upload.applied", "resolve.send", "finish.apply",
               "finish.ack", "finish.collect")
#: moments inside a round, from its start: the return of the send of a
#: round's REPLICATEs, and of everything else it sends (after the save)
ROUND_MARKS = ("replicates_out", "responses_out")
DEFAULT_ROUND_RING = 4096
#: the phase boundaries read the thread CPU clock in about one round an
#: engine per this long: a round is read with probability (the engine's
#: mean round) / (this), whatever the rounds before it were like.  On the
#: chip's host the read is a 5.6 us system call made holding the
#: interpreter lock, on a clock that ticks every 10 ms: read at every
#: boundary of every round it cost a 10 ms round 3-6% of its throughput
#: (PERF.md section 6, PR 37).  Where the mean round is this long or
#: longer every round is read
PHASE_CPU_EVERY_NS = 40_000_000
#: 2**32 / the golden ratio: ``seq * _WEYL mod 2**32`` is spread evenly
#: over any run of rounds AND over every n-th round of it (the engine
#: collects its statistics every tenth), which a fixed stride is not
_WEYL = 2654435769

_thread = threading.local()


def current_phase() -> str:
    """The round phase the calling thread is in, ``none`` outside one
    (the compile listener of capacity.py labels a compile with it)."""
    return getattr(_thread, "phase", "none")


class RoundBook:
    """Process-wide ring of round records, every engine's in one (the
    one-recorder doctrine of ``lifecycle.TRACER``): ``/trace`` renders it
    as one row per engine beside the proposal spans."""

    def __init__(self, ring_size: int = DEFAULT_ROUND_RING) -> None:
        self.mu = threading.Lock()
        self._ring = TraceRing(ring_size)     # guarded-by: mu

    def record(self, rec: dict) -> None:
        with self.mu:
            self._ring.append(rec)

    def rounds(self) -> list[dict]:
        """Retained round records, oldest first; they stay in the ring."""
        with self.mu:
            return self._ring.snapshot()

    def drain(self) -> list[dict]:
        """Return the retained records, oldest first, and clear."""
        with self.mu:
            return self._ring.drain()

    def counts(self) -> dict:
        with self.mu:
            return {"recorded": self._ring.appended,
                    "retained": len(self._ring),
                    "overwritten": self._ring.overwritten}

    def reset(self) -> None:
        with self.mu:
            self._ring.clear()

    def chrome_events(self) -> list[dict]:
        """The retained rounds as Chrome-trace events: one row per engine
        (``pid`` "engine", ``tid`` the engine's label), one complete
        event per phase entry and one instant event per mark, on the
        clock the lifecycle spans use.  A row's events are in clock order
        (an engine's rounds are recorded in order, a round's marks are
        sorted in among its phases), which the strict validator
        requires."""
        events = []
        for rec in self.rounds():
            entries = rec["phases"]
            args = {k: v for k, v in rec.items()
                    if k not in ("phases", "engine")}
            row = [{"name": phase, "cat": "round", "ph": "X", "ts": ts,
                    "dur": end - ts, "pid": "engine", "tid": rec["engine"],
                    "args": args}
                   for (phase, ts), (_next, end) in zip(entries, entries[1:])]
            row += [{"name": mark, "cat": "round", "ph": "i", "s": "t",
                     "ts": rec["t0_us"] + us, "pid": "engine",
                     "tid": rec["engine"]}
                    for mark, us in rec.get("marks", {}).items()]
            row.sort(key=lambda ev: ev["ts"])
            events += row
        return events


ROUNDS = RoundBook()


class _RoundSums:
    """What the timers of one registry summed over their committed rounds,
    in plain integers: CPU nanoseconds by phase, host nanoseconds by part,
    and by mark the nanoseconds from a round's start and the rounds that
    made it.  A timer adds to a row of its own (the engine thread is the
    row's one writer, so a round pays no lock, float or bucket search for
    them); four callback gauges add the rows up, in microseconds, when a
    snapshot is taken.  A timer that is collected leaves its row's sums
    behind, so the gauges never fall: its finalizer, which may run inside
    any allocation of any thread, takes no lock and only queues the row;
    the next reader folds it into ``_retired``."""

    FAMILIES = (
        ("engine_round_phase_cpu_us.sum", "phase", ROUND_PHASES[1:], 1e-3,
         "thread CPU time of the engine thread by phase of a round, "
         "summed over the rounds that read it (about one an engine per "
         "40 ms, drawn independently of the rounds before); the six sum "
         "to those rounds' engine_round_cpu_us"),
        ("engine_round_part_us.sum", "part", ROUND_PARTS, 1e-3,
         "host time of a part of a phase (inside its phase's "
         "engine_round_us), summed over the committed rounds "
         "(engine_round_us{phase=total} counts them)"),
        ("engine_round_mark_us.sum", "mark", ROUND_MARKS, 1e-3,
         "from a round's start to the return of the send of its "
         "REPLICATEs / of its other messages, summed over the rounds "
         "that sent"),
        ("engine_round_mark_us.count", "mark", ROUND_MARKS, 1,
         "the rounds that sent REPLICATEs / other messages"),
    )

    _of_registry: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
    _mu = threading.Lock()      # rows come and go; no round takes it

    def __init__(self, reg) -> None:
        self._rows: list[tuple[dict, ...]] = []           # guarded-by: _mu
        self._retired = self._new_row()                   # guarded-by: _mu
        self._gone: deque = deque()     # rows of collected timers, to fold
        for i, (name, label, _keys, scale, help) in enumerate(self.FAMILIES):
            reg.gauge_fn(name, lambda i=i, scale=scale: self._read(i, scale),
                         help=help, labelnames=(label,))

    def _new_row(self) -> tuple[dict, ...]:
        return tuple(dict.fromkeys(keys, 0)
                     for _name, _label, keys, _scale, _help in self.FAMILIES)

    @classmethod
    def row_for(cls, timer, reg) -> tuple[dict, ...]:
        """A fresh row for ``timer``, counted into ``reg``'s gauges: its
        dicts of phase CPU, parts, mark sums and mark counts."""
        with cls._mu:
            sums = cls._of_registry.get(reg)
            if sums is None:
                sums = cls._of_registry[reg] = cls(reg)
            row = sums._new_row()
            sums._rows.append(row)
        weakref.finalize(timer, sums._gone.append, row)
        return row

    def _read(self, i: int, scale) -> dict:
        with self._mu:
            while self._gone:
                row = self._gone.popleft()
                self._rows = [r for r in self._rows if r is not row]
                for kept, gone in zip(self._retired, row):
                    for k, v in gone.items():
                        kept[k] += v
            rows = [self._retired[i]] + [row[i] for row in self._rows]
            return {k: scale * sum(r[k] for r in rows) for k in rows[0]}


class _Part:
    """The host time of one part of the open round, as a context manager
    (``RoundTimer.part`` hands out the same object every round: a part is
    entered by one thread, never inside itself)."""

    __slots__ = ("_rt", "_name", "_t")

    def __init__(self, rt: "RoundTimer", name: str) -> None:
        self._rt, self._name = rt, name
        self._t: int | None = None

    def __enter__(self) -> "_Part":
        rt = self._rt
        self._t = None if rt._t0 is None else rt.clock_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if self._t is not None:
            self._rt.add(self._name, self._rt.clock_ns() - self._t)
        return False


class RoundTimer:
    """One engine's rounds, timed from inside.

    ``begin`` when ``step_all`` holds the engine lock, ``enter(phase)`` at
    each boundary (a phase may be entered more than once: its times add
    up), then ``commit`` for a round that dispatched or retired a step,
    or ``abandon`` for a pass that found nothing: that records nothing,
    and the next round's ``wait`` runs from the last RECORDED round.  As
    a context manager the timer begins on entry and abandons on exit
    whatever no ``commit`` closed: a pass that returns early, or raises.
    Every phase is observed once per round, 0 where it was not entered,
    so the phases' means sum to the mean of ``total``.

    The host clock is read at every boundary, and the thread CPU clock
    with it in about one round per ``PHASE_CPU_EVERY_NS`` (the whole
    round's CPU time, ``engine_round_cpu_us``, is read in every round, as
    ever): a phase's thread CPU time beside its host time (host time less
    CPU time is what the thread spent blocked in that phase: the device,
    the disk, the interpreter lock), the six summing to that round's
    ``engine_round_cpu_us``.  Which rounds are read is a draw on the
    round's number and the engine's slow mean round alone (``begin``),
    so a long round is as likely to be read as the short one after it.
    Inside a phase, ``part(name)`` (a context manager) or ``add(name,
    wall_ns)`` (for a part summed over rows in the caller's own
    variables) feeds the host time of one of ``ROUND_PARTS`` (no CPU
    time: the thread's CPU clock is a system call, 5.6 us a read on the
    chip's host, which a part entered once a row cannot pay), and ``mark(name)`` notes one
    of ``ROUND_MARKS`` at its distance from the round's start.  All
    three do nothing outside an open round.  Phases are observed into
    their histograms once a committed round, 0 where not entered; parts,
    phase CPU times and marks are added to plain sums there
    (``_RoundSums``: parts every committed round, phase CPU times in a
    round that read them, a mark only in a round that made it), since
    their readers take window means and nothing else.

    In a capture a pass is a ``kernel_engine.stage`` annotation before
    the engine knows whether it is a round; ``abandon`` marks the one of
    an idle pass with ``idle=1`` metadata, so a reader of the capture
    counts the rounds the registry counts.  Parts are no annotations (one
    a row would swamp a capture): the first annotation written into a
    capture is preceded by a ``tracing.clock_sync`` mark, which puts the
    round records on the capture's clock.

    Called by the engine thread under the engine lock, so it holds no
    lock of its own.  Both clocks are injected (nanoseconds; tests pass
    counters)."""

    def __init__(self, metrics, prefix: str, engine: str = "",
                 registry=None, book: RoundBook | None = None,
                 clock_ns=None, cpu_clock_ns=None) -> None:
        self.metrics = metrics
        self.prefix = prefix
        self.engine = engine
        self.clock_ns = clock_ns if clock_ns is not None else time.monotonic_ns
        self.cpu_clock_ns = (cpu_clock_ns if cpu_clock_ns is not None
                             else time.thread_time_ns)
        reg = registry if registry is not None else telemetry.GLOBAL
        fam = reg.histogram(
            "engine_round_us",
            help="host time of an engine round by phase (total = stage.."
                 "finish; wait = end of the last round to this one)",
            labelnames=("phase",))
        self._hist = {p: fam.labels(p) for p in ROUND_PHASES + (ROUND_TOTAL,)}
        self._cpu_hist = reg.histogram(
            "engine_round_cpu_us",
            help="thread CPU time of the engine thread over a round's "
                 "total (total minus this = blocked: device, disk, GIL)")
        (self._phase_cpu_sum, self._part_sum, self._mark_sum,
         self._mark_n) = _RoundSums.row_for(self, reg)
        self._book = book if book is not None else ROUNDS
        self._seq = 0
        self._ewma_us = 0.0
        self._last_end: int | None = None     # end of the last recorded round
        self._t0: int | None = None           # None: no round open
        self._cpu0 = 0
        self._cur = ""
        self._cur_t = self._cur_cpu = 0
        # whether the open round reads the CPU clock at its boundaries,
        # and the engine's mean round (ns, over its last ~64 rounds; slow,
        # so that one long round hardly moves the chance of the next)
        self._phase_cpu = False
        self._mean_ns = PHASE_CPU_EVERY_NS
        # the open round's host and CPU time per phase, its parts, its
        # marks and its phase entries; commit and abandon leave all of
        # them empty for the next pass
        self._acc = dict.fromkeys(ROUND_PHASES[1:], 0)
        self._cpu_acc = dict.fromkeys(ROUND_PHASES[1:], 0)
        self._part_acc = dict.fromkeys(ROUND_PARTS, 0)
        self._sent: dict[str, int] = {}
        self._marks: list[tuple[str, int]] = []
        self._dirty = False     # a phase or part of the open round has time
        self._parts = {p: _Part(self, p) for p in ROUND_PARTS}
        self._ann = None

    # -- the round --------------------------------------------------------

    def _annotate(self, phase: str | None) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        trace_dir = _active_trace_dir
        if _synced_dir != trace_dir:
            # a capture began, or ended, however it was armed or stopped
            _sync_capture(trace_dir)
        if trace_dir is not None and phase is not None:
            self._ann = annotate(f"kernel_engine.{phase}",
                                 engine=self.engine)
            self._ann.__enter__()

    def __enter__(self) -> "RoundTimer":
        self.begin()
        return self

    def __exit__(self, *exc) -> bool:
        self.abandon()
        return False

    def begin(self) -> None:
        now = self.clock_ns()
        self._t0 = self._cur_t = now
        self._cpu0 = self._cur_cpu = self.cpu_clock_ns()
        self._phase_cpu = ((self._seq * _WEYL & 0xFFFFFFFF)
                           * PHASE_CPU_EVERY_NS < self._mean_ns << 32)
        self._cur = "stage"
        self._marks.append(("stage", now))
        _thread.phase = "stage"
        self._annotate("stage")

    def enter(self, phase: str) -> None:
        if self._t0 is None:
            return
        now = self.clock_ns()
        self._acc[self._cur] += now - self._cur_t
        if self._phase_cpu:
            cpu = self.cpu_clock_ns()
            self._cpu_acc[self._cur] += cpu - self._cur_cpu
            self._cur_cpu = cpu
        self._dirty = True
        self._cur, self._cur_t = phase, now
        self._marks.append((phase, now))
        _thread.phase = phase
        self._annotate(phase)

    def part(self, name: str) -> _Part:
        """Time the body as part ``name`` of the open round (entered more
        than once a round, its times add up)."""
        return self._parts[name]

    def add(self, name: str, wall_ns: int) -> None:
        """Add to part ``name`` of the open round what the caller timed
        itself (``clock_ns``)."""
        if self._t0 is not None:
            self._dirty = True
            self._part_acc[name] += wall_ns

    def mark(self, name: str) -> None:
        """Note that the open round reached ``name`` now."""
        if self._t0 is not None:
            self._sent[name] = self.clock_ns() - self._t0

    @contextlib.contextmanager
    def within(self, name: str):
        """An enclosing annotation (the engine's two older names) around
        part of a round.  The phase annotation open at either end is
        closed first, whether the body returns or raises, so the phases
        nest inside it; a phase's time runs on to the next ``enter``."""
        self._annotate(None)
        with annotate(name):
            try:
                yield
            finally:
                self._annotate(None)

    def _clear(self) -> None:
        """Close the open round: nothing of it is left for the next pass
        (an idle pass, the common one, has entered nothing)."""
        self._t0 = None
        self._marks.clear()
        if self._sent:
            self._sent = {}
        if self._dirty:
            self._dirty = False
            self._acc = dict.fromkeys(ROUND_PHASES[1:], 0)
            self._cpu_acc = dict.fromkeys(ROUND_PHASES[1:], 0)
            self._part_acc = dict.fromkeys(ROUND_PARTS, 0)
        _thread.phase = "none"

    def abandon(self) -> None:
        """Drop the open round (a pass that found nothing to do, or
        raised): nothing is recorded, and its ``stage`` annotation, where
        still open, is marked ``idle=1``."""
        if self._t0 is None:
            return
        ann = self._ann
        if ann is not None and self._cur == "stage":
            with contextlib.suppress(Exception):
                ann.set_metadata(idle=1)
        self._annotate(None)
        self._clear()

    def commit(self, **counts) -> None:
        """End the round and feed the three sinks; ``counts`` ride the
        round's record (staging counts, the sampled lifecycle keys)."""
        if self._t0 is None:
            return
        now, cpu = self.clock_ns(), self.cpu_clock_ns()
        self._annotate(None)
        t0 = self._t0
        acc = self._acc
        acc[self._cur] += now - self._cur_t
        self._dirty = True
        for phase, ns in acc.items():
            self._hist[phase].observe(ns / 1e3)
        if self._phase_cpu:
            cpu_acc, cpu_sum = self._cpu_acc, self._phase_cpu_sum
            cpu_acc[self._cur] += cpu - self._cur_cpu
            for phase, ns in cpu_acc.items():
                cpu_sum[phase] += ns
        total_ns = now - t0
        total_us = total_ns / 1e3
        self._hist[ROUND_TOTAL].observe(total_us)
        cpu_us = (cpu - self._cpu0) / 1e3
        self._cpu_hist.observe(cpu_us)
        self._mean_ns += (min(total_ns, PHASE_CPU_EVERY_NS)
                          - self._mean_ns) >> 6
        parts, part_sum = {}, self._part_sum
        for part, ns in self._part_acc.items():
            part_sum[part] += ns
            parts[part] = ns // 1000
        sent, self._sent = self._sent, {}
        for mark, ns in sent.items():
            self._mark_sum[mark] += ns
            self._mark_n[mark] += 1
            sent[mark] = ns // 1000
        marks = self._marks
        if self._last_end is not None:
            self._hist["wait"].observe((t0 - self._last_end) / 1e3)
            marks.insert(0, ("wait", self._last_end))
        self._last_end = now
        self._ewma_us = total_us if self._ewma_us == 0 else (
            0.9 * self._ewma_us + 0.1 * total_us)
        self.metrics.set(f"{self.prefix}.ewma_us", int(self._ewma_us))
        self._seq += 1
        rec = {"engine": self.engine, "seq": self._seq, "t0_us": t0 // 1000,
               "phases": [(p, t // 1000) for p, t in marks]
               + [("end", now // 1000)],
               "cpu_us": int(cpu_us), "parts": parts, "marks": sent,
               **counts}
        self._clear()
        self._book.record(rec)


telemetry.GLOBAL.gauge_fn(
    "process_cpu_us", lambda: time.process_time_ns() // 1000,
    help="CPU time of the whole process, every thread's (beside the "
         "engine threads' engine_round_cpu_us), read when a snapshot is "
         "taken")

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
  on the host clock: ``engine_round_us{phase=...}`` /
  ``engine_round_cpu_us`` histograms in ``telemetry.GLOBAL``, a
  ``kernel_engine.<phase>`` annotation per phase while a capture is
  armed, a bounded ring of round records for ``/trace``, and the
  ``<prefix>.ewma_us`` gauge the load controller reads.  Always on (the
  profiler itself is opt-in: capture costs memory).

Environment: ``DRAGONBOAT_TPU_TRACE_DIR`` arms profiler capture at import
of the engine, for drive-by profiling without code changes.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque

from dragonboat_tpu import telemetry

_active_trace_dir: str | None = None
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
    _clock_sync()


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
DEFAULT_ROUND_RING = 4096

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
        event per phase entry, on the clock the lifecycle spans use.  A
        row's events are in clock order (an engine's rounds are recorded
        in order), which the strict validator requires."""
        events = []
        for rec in self.rounds():
            marks = rec["phases"]
            args = {k: v for k, v in rec.items()
                    if k not in ("phases", "engine")}
            for (phase, ts), (_next, end) in zip(marks, marks[1:]):
                events.append({
                    "name": phase, "cat": "round", "ph": "X", "ts": ts,
                    "dur": end - ts, "pid": "engine", "tid": rec["engine"],
                    "args": args})
        return events


ROUNDS = RoundBook()


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

    In a capture a pass is a ``kernel_engine.stage`` annotation before
    the engine knows whether it is a round; ``abandon`` marks the one of
    an idle pass with ``idle=1`` metadata, so a reader of the capture
    counts the rounds the registry counts.

    Called by the engine thread under the engine lock, so it holds no
    lock of its own.  Both clocks are injected (nanoseconds; tests pass
    counters)."""

    def __init__(self, metrics, prefix: str, engine: str = "",
                 registry=None, book: RoundBook | None = None,
                 clock_ns=None, cpu_clock_ns=None) -> None:
        self.metrics = metrics
        self.prefix = prefix
        self.engine = engine
        self._clock = clock_ns if clock_ns is not None else time.monotonic_ns
        self._cpu_clock = (cpu_clock_ns if cpu_clock_ns is not None
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
        self._book = book if book is not None else ROUNDS
        self._seq = 0
        self._ewma_us = 0.0
        self._last_end: int | None = None     # end of the last recorded round
        self._t0: int | None = None           # None: no round open
        self._cpu0 = 0
        self._cur = ""
        self._cur_t = 0
        # the open round's time per phase and its phase entries; commit
        # and abandon leave both empty for the next pass
        self._acc = dict.fromkeys(ROUND_PHASES[1:], 0)
        self._marks: list[tuple[str, int]] = []
        self._ann = None

    # -- the round --------------------------------------------------------

    def _annotate(self, phase: str | None) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if phase is not None and _active_trace_dir is not None:
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
        now = self._clock()
        self._t0, self._cpu0 = now, self._cpu_clock()
        self._cur, self._cur_t = "stage", now
        self._marks.append(("stage", now))
        _thread.phase = "stage"
        self._annotate("stage")

    def enter(self, phase: str) -> None:
        if self._t0 is None:
            return
        now = self._clock()
        self._acc[self._cur] += now - self._cur_t
        self._cur, self._cur_t = phase, now
        self._marks.append((phase, now))
        _thread.phase = phase
        self._annotate(phase)

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
        self._t0 = None
        self._marks.clear()
        for phase in self._acc:
            self._acc[phase] = 0
        _thread.phase = "none"

    def commit(self, **counts) -> None:
        """End the round and feed the three sinks; ``counts`` ride the
        round's record (staging counts, the sampled lifecycle keys)."""
        if self._t0 is None:
            return
        now, cpu = self._clock(), self._cpu_clock()
        self._annotate(None)
        _thread.phase = "none"
        t0, self._t0 = self._t0, None
        acc = self._acc
        acc[self._cur] += now - self._cur_t
        for phase, ns in acc.items():
            self._hist[phase].observe(ns / 1e3)
            acc[phase] = 0
        total_us = (now - t0) / 1e3
        self._hist[ROUND_TOTAL].observe(total_us)
        cpu_us = (cpu - self._cpu0) / 1e3
        self._cpu_hist.observe(cpu_us)
        marks, self._marks = self._marks, []
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
               "cpu_us": int(cpu_us), **counts}
        self._book.record(rec)

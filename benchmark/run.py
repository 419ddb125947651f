#!/usr/bin/env python
"""Run one cell of ``BENCHMARK.json`` in one process, on the chip.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): refuse a backend that is not the TPU or has
fewer chips than the cell asks for; turn the persistent compile cache on;
build the cell's deployment through ``NodeHost`` (``deployment.py``); drive
the cell's own traffic until every jit entry has compiled.  Then the
measured window of ``--seconds``, fed by the same client threads without a
pause; then stop issuing, drain what is in flight, check the answers
(``checker.py``), close.  A compile or retrace inside the window fails the
run.  The last stdout line is the contract's result object; every earlier
line is one JSON object that says what the last line rests on.

Outside the driver's four arguments:

    --rehearse        the only way this runs off the chip: CPU backend, the
                      configuration's rehearsal size; every metric value is
                      withheld (null), so no CPU number carries a device
                      metric's name
    --control FAULT   the control of ``correct``: the state machines break one
                      stated guarantee (``deployment.FAULTS``); the run must
                      come out ``"correct": false``
    --episodes LIST   several short windows on one deployment in one process,
                      ``seed[:fault],...`` — for reading many seeds and the
                      control where set-up is long; prints one result line
                      per episode and no contract line
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.monotonic()      # before the heavy imports: set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:            # `python benchmark/run.py` works too
    sys.path.insert(0, ROOT)

import jax  # noqa: E402  (touches no backend until require_device)

import dragonboat_tpu  # noqa: E402
from dragonboat_tpu import (  # noqa: E402
    capacity, hostenv, lifecycle, native, telemetry, tracing,
)

from benchmark import answers, checker, layers, peaks, stats  # noqa: E402
from benchmark import traffic as gen, xplane  # noqa: E402
from benchmark.deployment import (  # noqa: E402
    FAULTS, BenchFailure, Deployment, check, load_json,
)

CAPTURE_LEAD_S = 1.0
WARMUP_DEADLINE_S = 180.0
WARMUP_QUIET_S = 3.0         # no compile for this long ends the warm-up


def say(**fields) -> None:
    """One JSON line; ``t`` is seconds since the process started."""
    print(json.dumps({"t": round(time.monotonic() - _PROCESS_T0, 3), **fields},
                     default=str), flush=True)


class CompileLog:
    """Every XLA compile of the process, tracked entry or not, from jax's
    own monitoring events: the engine also jits small programs per shape
    (gathers over the rows a step saved) that ``capacity.TRACKER`` does not
    wrap, and a compile stalls an engine round wherever it lands."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.times: list[tuple[float, float]] = []    # (monotonic s, secs)
        self.cache: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.times.append((time.monotonic(), secs))

    def _event(self, event: str, **_) -> None:
        self.cache[event] += 1

    def last(self) -> float:
        return self.times[-1][0] if self.times else 0.0

    def between(self, t0: float, t1: float) -> dict:
        hits = [secs for t, secs in self.times if t0 <= t <= t1]
        return {"compiles": len(hits), "seconds": sum(hits)}


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=FAULTS, default=None)
    ap.add_argument("--episodes", default=None)
    return ap.parse_args(argv)


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"benchmark: no workload {name!r}; have {sorted(cells)}")
    return bench, cells[name]


def metrics_for(bench: dict, section: str, cell: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def require_device(chips: int, rehearse: bool):
    """-> jax.devices(), or exit non-zero before anything is built."""
    if rehearse and chips > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            sys.exit(f"benchmark: --rehearse is for the CPU backend; jax "
                     f"reports {platform!r}")
    elif platform != "tpu":
        sys.exit(f"benchmark: no accelerator — jax reports platform "
                 f"{platform!r}; refusing to carry on")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), jax "
                 f"reports {len(devices)}")
    return devices


class Window:
    """The measured window as the end-to-end readers see it."""

    def __init__(self, records, start_ns: int, end_ns: int,
                 setup_s: float) -> None:
        self.start_ns, self.end_ns, self.setup_s = start_ns, end_ns, setup_s
        self.seconds = (end_ns - start_ns) / 1e9
        #: operations whose outcome arrived inside the window
        self.finished = [r for r in records
                         if start_ns <= r.ret_ns <= end_ns]
        self.acked = [r for r in self.finished if r.status == gen.OK]

    def acked_of(self, kind: str):
        return [r for r in self.acked if r.kind == kind]

    def latencies_ms(self, kind: str) -> list[float]:
        return [stats.ns_to_ms(r.ret_ns - r.call_ns)
                for r in self.acked_of(kind)]


# -- one episode: warm-up, window, drain, checks ------------------------------

def start_capture(trace_dir: str) -> None:
    """A device capture without the Python tracer (150 host threads of
    Python calls would swamp it).  ``tracing.start_trace`` takes no
    profiler options, so the program's flag that arms its two annotations
    is set here beside the profiler's own start (PERF.md, open questions)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    tracing._active_trace_dir = trace_dir


def stop_capture() -> None:
    tracing._active_trace_dir = None
    jax.profiler.stop_trace()


def sleep_until(t: float, poll=None) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        if poll is not None:
            poll()
        time.sleep(min(0.25, left))


@dataclass
class Cell:
    """What every episode of one process shares."""
    bench: dict            # BENCHMARK.json
    entry: dict            # this cell's entry of ``workloads``
    cfg: dict              # its configuration file
    traffic: dict          # its traffic file (rehearsal overrides applied)
    root: str              # the run's scratch directory
    rehearse: bool
    compiles: CompileLog


@dataclass
class Driven:
    """What one warm-up, window and drain left behind."""
    records: list
    start_ns: int
    end_ns: int
    tracker: tuple[dict, dict]      # capacity.TRACKER rows at the two ends
    registry: tuple[dict, dict]     # telemetry registry at the two ends
    spans: list                     # lifecycle traces completed in the window
    trace_dir: str | None           # where the device capture lies, if any
    clients_most_records: int


def drive(dep, cell: Cell, seed: int, seconds: float, trace: bool,
          first_key: int) -> Driven:
    """Warm up with the cell's own traffic, measure ``seconds`` without a
    pause in the load, stop issuing and drain."""
    cfg, traffic, compiles = cell.cfg, cell.traffic, cell.compiles
    lifecycle.TRACER.reset()
    load = gen.Load(dep, traffic, gen.client_streams(
        traffic, seed, dep.shards, first_key))
    t_load = time.monotonic()
    load.start()

    # warm-up: until the minimum time has passed, every client has had
    # replies, and nothing has compiled for a while (every shape the window
    # uses compiles in here)
    least = int(traffic["warmup_acks_per_thread"])
    sleep_until(t_load + float(traffic["warmup_s"]))
    while (min(load.finished_per_client()) < least
           or time.monotonic() - compiles.last() < WARMUP_QUIET_S):
        check(time.monotonic() - t_load < WARMUP_DEADLINE_S,
              f"warm-up: after {WARMUP_DEADLINE_S:.0f} s a client has under "
              f"{least} replies or programs still compile: "
              f"{load.finished_per_client()}")
        time.sleep(0.05)
    leaders0 = dict(dep.leaders)

    spans: dict[int, dict] = {}

    def poll_spans() -> None:
        # the tracer's ring holds 256 traces: read it before it turns over
        for tr in lifecycle.TRACER.completed():
            spans[tr["key"]] = tr

    poll = poll_spans if trace else None
    tracker0, registry0 = (capacity.TRACKER.snapshot(),
                           telemetry.GLOBAL.snapshot())
    w0 = time.monotonic_ns()
    trace_dir = (os.path.join(cell.root, f"capture-{seed}") if trace
                 else None)
    if trace:
        capture_s = min(float(cfg.get("capture_seconds", 3.0)),
                        max(0.5, seconds - 2 * CAPTURE_LEAD_S))
        sleep_until(w0 / 1e9 + CAPTURE_LEAD_S, poll)
        start_capture(trace_dir)
        sleep_until(time.monotonic() + capture_s, poll)
        stop_capture()
    sleep_until(w0 / 1e9 + seconds, poll)
    w1 = time.monotonic_ns()
    tracker1, registry1 = (capacity.TRACKER.snapshot(),
                           telemetry.GLOBAL.snapshot())
    if trace:
        poll_spans()
    records = load.join(float(traffic["request_timeout_s"]) + 30.0)
    say(phase="drained", seed=seed, warmup_s=w0 / 1e9 - t_load,
        window_s=(w1 - w0) / 1e9, drain_s=time.monotonic() - w1 / 1e9,
        operations=len(records),
        transient_retries=load.transient_retries(),
        transient_retries_of_window_operations=sum(
            r.retries for r in records if w0 <= r.ret_ns <= w1),
        leaders_moved_since_warmup=sum(
            dep.leader_host(sid) != leaders0[sid] for sid in dep.shards),
        compiles_in_warmup=compiles.between(t_load, w0 / 1e9),
        compiles_in_window=compiles.between(w0 / 1e9, w1 / 1e9))

    compiled = {e: (tracker0.get(e, {}).get("compiles", 0), row["compiles"])
                for e, row in tracker1.items()
                if row["compiles"] != tracker0.get(e, {}).get("compiles", 0)
                or row["retraces"] != tracker0.get(e, {}).get("retraces", 0)}
    check(not compiled, f"compiled or retraced inside the window: {compiled}")
    return Driven(
        records, w0, w1, (tracker0, tracker1), (registry0, registry1),
        [tr for tr in spans.values()
         if w0 // 1000 <= tr["stamps"][-1][1] <= w1 // 1000],
        trace_dir, max(len(c.records) for c in load.clients))


def read_capture(trace_dir: str, chips: int, rehearse: bool) -> dict | None:
    """The device capture, reduced.  Off the chip (a rehearsal) there is no
    device plane and this reads nothing; on the chip that fails the run."""
    try:
        capture = xplane.reduce_capture(xplane.load(trace_dir), chips)
    except (FileNotFoundError, ValueError) as e:
        check(rehearse, f"the device capture cannot be read: {e}")
        say(phase="capture", note=f"no device capture off the chip: {e}")
        return None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return capture


def per_layer_view(dep, cell: Cell, driven: Driven,
                   window: Window) -> layers.RunView:
    chips, rehearse = cell.entry["chips"], cell.rehearse
    capture = read_capture(driven.trace_dir, chips, rehearse)
    view = layers.RunView(
        config=cell.cfg, traffic=cell.traffic, chips=chips,
        engines=len(dep.engines), device_kind=dep.devices[0].device_kind,
        window_s=window.seconds,
        acked_writes=len(window.acked_of(gen.WRITE)),
        acked_reads=len(window.acked_of(gen.READ)),
        write_latencies_ms=window.latencies_ms(gen.WRITE),
        tracker_before=driven.tracker[0], tracker_after=driven.tracker[1],
        registry_before=driven.registry[0], registry_after=driven.registry[1],
        spans=driven.spans, capture=capture,
        step_bytes=(dep.step_bytes_per_device() if capture or rehearse
                    else None))
    say(phase="traced", sampled_spans=len(view.spans),
        sampled_by_kind=dict(Counter(tr["kind"] for tr in view.spans)),
        step_bytes=view.step_bytes,
        capture=None if capture is None else {
            k: v for k, v in capture.items() if k != "breakdown"})
    return view


def run_episode(dep, cell: Cell, seed: int, seconds: float, trace: bool,
                fault: str | None, initial: dict, first_key: int,
                setup_t0: float | None) -> tuple[dict, int]:
    """One warm-up, window, drain and check on a standing deployment.
    -> (the contract's result object, the first new-key number the next
    episode on this deployment may use)."""
    traffic = cell.traffic
    dep.switch.fault = fault
    driven = drive(dep, cell, seed, seconds, trace, first_key)
    setup_s = (driven.start_ns / 1e9 - setup_t0
               if setup_t0 is not None else None)
    window = Window(driven.records, driven.start_ns, driven.end_ns, setup_s)
    fsync_key = "logdb.fsync_us.count"
    fsyncs = int(driven.registry[1].get(fsync_key, 0)
                 - driven.registry[0].get(fsync_key, 0))
    numbers = answers.check_answers(dep, traffic, driven.records, initial,
                                    seed, fsyncs, control=fault is not None)
    for n in numbers:
        say(phase="check", seed=seed, fault=fault, **n)

    writes, reads = window.acked_of(gen.WRITE), window.acked_of(gen.READ)
    say(phase="window", seed=seed, acked_writes=len(writes),
        acked_reads=len(reads), write_latency_samples=len(writes),
        read_latency_samples=len(reads), finished=len(window.finished),
        not_acknowledged=len(window.finished) - len(window.acked),
        fsyncs=fsyncs,
        compiles={e: {k: row[k] for k in ("calls", "compiles", "retraces")}
                  for e, row in sorted(driven.tracker[1].items())})
    check(window.acked, "nothing was acknowledged inside the window")

    capture = None
    if trace:
        subject = per_layer_view(dep, cell, driven, window)
        capture = subject.capture
        section, kind = "per_layer", "layer_metrics"
    else:
        subject, section, kind = window, "end_to_end", "end_to_end"
    metrics: dict = {}
    for m in metrics_for(cell.bench, section, cell.entry["name"]):
        value = layers.load_reader(m["name"], kind)(subject)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dep.devices[0].platform,
              "kind": dep.devices[0].device_kind, "count": len(dep.devices),
              "memory_peak_bytes": dep.memory_peak_bytes()}
    result = {"correct": checker.verdict(numbers),
              "attempted": len(window.finished),
              "failed": len(window.finished) - len(window.acked),
              "metrics": metrics, "device": device}
    if capture is not None:
        device["busy_s"] = capture["busy_s"]
        device["window_s"] = capture["window_s"]
        result["breakdown"] = capture["breakdown"]
    if cell.rehearse:
        # a CPU number never carries a device metric's name
        for m in metrics.values():
            m["value"] = None
        for key in ("memory_peak_bytes", "busy_s", "window_s"):
            device.pop(key, None)
        result.pop("breakdown", None)
        result["rehearsal"] = True
    return result, (first_key + driven.clients_most_records
                    + int(traffic["in_flight_per_thread"]))


def parse_episodes(args) -> list[tuple[int, str | None]]:
    if not args.episodes:
        return [(args.seed, args.control)]
    out = []
    for item in args.episodes.split(","):
        seed, _, fault = item.partition(":")
        if fault and fault not in FAULTS:
            sys.exit(f"benchmark: unknown fault {fault!r}; have {FAULTS}")
        out.append((int(seed), fault or None))
    return out


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    bench, entry = load_cell(args.workload)
    devices = require_device(entry["chips"], args.rehearse)
    if not args.rehearse:
        peaks.peaks_of(devices[0].device_kind)     # unknown device: error

    # the system under test is this checkout's, not one found elsewhere
    program = os.path.dirname(getattr(dragonboat_tpu, "__file__", None) or "")
    if os.path.realpath(program) != os.path.realpath(
            os.path.join(ROOT, "dragonboat_tpu")):
        sys.exit(f"benchmark: dragonboat_tpu is not this checkout's "
                 f"({program or 'no package file'})")

    cfg = load_json("configs", entry["config"])
    traffic = load_json("traffic", entry["traffic"])
    if args.rehearse:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    gen.validate(traffic)
    shards = cfg["rehearsal"]["shards"] if args.rehearse else cfg["shards"]
    episodes = parse_episodes(args)

    entries_before = hostenv.cache_entry_count()
    cache_dir = hostenv.enable_compile_cache()
    compiles = CompileLog()
    say(phase="start", workload=entry["name"], config=cfg["name"],
        traffic=traffic["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearse=args.rehearse, episodes=episodes,
        device={"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)},
        jax=jax.__version__, python=sys.version.split()[0],
        compile_cache_dir=cache_dir, cache_entries_before=entries_before,
        native_replay_library=native.available(),
        message_delay_ms=cfg["message_delay_ms"],
        guarantees=cfg["guarantees"])

    root = tempfile.mkdtemp(prefix="benchmark-")
    cell = Cell(bench, entry, cfg, traffic, root, args.rehearse, compiles)
    dep = None
    results = []
    try:
        dep = Deployment(
            cfg, devices, root, shards,
            int(traffic["trace_sample_every"]) if args.trace else None, say)
        t0 = time.monotonic()
        initial = gen.preload(dep, traffic, episodes[0][0])
        if initial:
            say(phase="preloaded", keys=len(initial),
                seconds=time.monotonic() - t0)
        first_key = 0
        for i, (seed, fault) in enumerate(episodes):
            if i and initial:
                # the values the last episode left are this one's initial
                initial = {(sid, k): v for sid in dep.shards
                           for k, v in dep.replica_items(
                               dep.leaders[sid], sid).items()}
            result, first_key = run_episode(
                dep, cell, seed, args.seconds, bool(args.trace), fault,
                initial, first_key, _PROCESS_T0 if i == 0 else None)
            results.append(result)
            if len(episodes) > 1:
                say(phase="episode", seed=seed, fault=fault, **result)
    finally:
        if dep is not None:
            dep.close()
        shutil.rmtree(root, ignore_errors=True)
    say(phase="done", cache_entries_after=hostenv.cache_entry_count(),
        cache_hits=compiles.cache["/jax/compilation_cache/cache_hits"],
        cache_misses=compiles.cache["/jax/compilation_cache/cache_misses"],
        compiles=compiles.between(0.0, time.monotonic()),
        process_s=time.monotonic() - _PROCESS_T0)
    if len(episodes) == 1:
        print(json.dumps(results[0]), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        sys.exit(f"benchmark: {e}")

"""What a per-layer metric's reader is given, and how readers are found.

A per-layer metric named ``m`` in ``BENCHMARK.json`` is read by
``benchmark/layer_metrics/m.py``: a module with ``read(run) -> number or
None``.  A reader that finds nothing to read returns None and the harness
leaves the metric out of the line.  Adding a metric is adding that file and
an entry; nothing here is edited.
"""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass, field

from benchmark import stats

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class RunView:
    """One traced run, as the readers see it."""
    config: dict
    traffic: dict
    chips: int
    engines: int
    device_kind: str
    window_s: float
    #: acknowledged operations of the window, by kind
    acked_writes: int
    acked_reads: int
    #: client-side latencies (ms) of the window's acknowledged writes
    write_latencies_ms: list
    #: capacity.TRACKER rows {entry: {"calls", "compiles", ...}} at the
    #: window's two ends
    tracker_before: dict
    tracker_after: dict
    #: telemetry registry snapshots at the window's two ends
    registry_before: dict
    registry_after: dict
    #: lifecycle traces completed inside the window
    spans: list = field(default_factory=list)
    #: xplane.reduce_capture's result
    capture: dict | None = None
    #: peaks.step_bytes_per_device for the engine's step, as served
    step_bytes: int | None = None

    def step_calls(self, snapshot: dict) -> int:
        return sum(snapshot.get(entry, {}).get("calls", 0)
                   for entry in self.config["step_entries"])

    def window_step_calls(self) -> int:
        return (self.step_calls(self.tracker_after)
                - self.step_calls(self.tracker_before))

    def step_program(self) -> dict | None:
        """Calls and device seconds of the step program in the capture,
        summed over the chips that ran it (one call per chip per step)."""
        if self.capture is None:
            return None
        rows = [self.capture["programs"][p]
                for p in self.config["step_programs"]
                if p in self.capture["programs"]]
        if not rows:
            return None
        calls = sum(r["calls"] for r in rows)
        return {"calls": calls, "seconds": sum(r["seconds"] for r in rows),
                "steps": calls / self.capture["devices_with_operations"]}


def dwell_ms(run: RunView, kind: str, stages) -> float | None:
    """Median over the window's sampled spans of ``kind`` of the time spent
    in ``stages``: a stage's dwell is its stamp minus the stamp before it
    (``lifecycle.py``'s own attribution), summed where a span holds the
    stage more than once.  None where no span holds any of them."""
    per_span = []
    for tr in run.spans:
        if tr["kind"] != kind:
            continue
        stamps = tr["stamps"]
        us = [ts - stamps[i][1] for i, (stage, ts) in enumerate(stamps[1:])
              if stage in stages]
        if us:
            per_span.append(sum(us) / 1e3)
    return stats.median(per_span) if per_span else None


def load_reader(name: str, kind: str = "layer_metrics"):
    """``benchmark/<kind>/<name>.py``'s ``read``; ``kind`` is
    ``layer_metrics`` (given a RunView) or ``end_to_end`` (given run.py's
    Window)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read

"""Reduction of a ``jax.profiler`` capture (``*.xplane.pb``) to numbers:
device-busy union, time per program, time per operation, collectives, and
the idle gaps attributed to what the host was doing.

Reads the capture with ``jax.profiler.ProfileData`` alone.  Works on any
object with the same shape (planes -> lines -> events with ``name``,
``start_ns``, ``duration_ns``), which is how the tests feed it a small
synthetic capture.  Intervals are (start_ns, end_ns) pairs.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"     # a collective issued as start/done pairs
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|all-to-all|collective-permute|"
    r"reduce-scatter)")
#: the two annotations the program has, in the order a gap is given to them
HOST_SPANS = ("kernel_engine.step", "kernel_engine.process_outputs")
UNATTRIBUTED = "unattributed"


def find_capture(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_capture(trace_dir))


# -- interval arithmetic ------------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of the intervals (empty ones dropped)."""
    out: list[list[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list[tuple[float, float]]:
    """Of two sorted disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list[tuple[float, float]]:
    """``xs`` minus ``ys``, both sorted and disjoint."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# -- the reduction ------------------------------------------------------------

def _span(ev) -> tuple[float, float]:
    return (ev.start_ns, ev.start_ns + ev.duration_ns)


def program_of(event_name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def op_of(event_name: str) -> str:
    """The TPU's operation line names an event by its whole HLO text,
    ``%while.71 = (s32[]{...}, ...) while(...)``; keep ``while.71``."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


def reduce_capture(profile, chips: int, top: int = 10) -> dict:
    """-> the numbers the per-layer readers and the result line take.

    ``chips`` is what the cell asks for: busy time is averaged over them, so
    a chip that holds no state counts as idle, not as absent."""
    device_ops: dict[int, list] = {}
    device_modules: dict[int, list] = {}
    async_ops: list = []
    host_spans: dict[str, list] = {name: [] for name in HOST_SPANS}
    lo, hi = float("inf"), float("-inf")
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(int(m.group(1)), []).extend(
                        line.events)
                elif line.name == MODULES_LINE:
                    device_modules.setdefault(int(m.group(1)), []).extend(
                        line.events)
                elif line.name == ASYNC_OPS_LINE:
                    async_ops.extend(line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    lo = min(lo, ev.start_ns)
                    hi = max(hi, ev.start_ns + ev.duration_ns)
                    if ev.name in host_spans:
                        host_spans[ev.name].append(_span(ev))
    # busy: the union of operation intervals; a device whose capture has no
    # operation line falls back to its program line
    busy_by_device = {}
    for dev in sorted(set(device_ops) | set(device_modules)):
        events = device_ops.get(dev) or device_modules.get(dev, [])
        busy_by_device[dev] = union(_span(e) for e in events)
        for a, b in busy_by_device[dev][:1] + busy_by_device[dev][-1:]:
            lo, hi = min(lo, a), max(hi, b)
    if not any(busy_by_device.values()):
        raise ValueError("the capture holds no device operation")
    window_ns = hi - lo

    programs: dict[str, dict] = {}
    for events in device_modules.values():
        for ev in events:
            row = programs.setdefault(program_of(ev.name),
                                      {"calls": 0, "seconds": 0.0})
            row["calls"] += 1
            row["seconds"] += ev.duration_ns / 1e9
    ops: dict[str, float] = {}
    for events in device_ops.values():
        for ev in events:
            name = op_of(ev.name)
            ops[name] = ops.get(name, 0.0) + ev.duration_ns / 1e9
    # a synchronous collective is one event of the operation line; an
    # asynchronous one is a span of the async line (its -start and -done
    # halves on the operation line are not the transfer)
    collectives = [
        ev for events in device_ops.values() for ev in events
        if COLLECTIVE.match(op_of(ev.name))
        and "-start" not in op_of(ev.name) and "-done" not in op_of(ev.name)
    ] + [ev for ev in async_ops if COLLECTIVE.match(op_of(ev.name))]
    collective_s = sum(ev.duration_ns for ev in collectives) / 1e9
    collective_calls = len(collectives)

    # idle gaps of the busiest device, by what the host was doing
    busiest = max(busy_by_device, key=lambda d: total(busy_by_device[d]))
    gaps = subtract([(lo, hi)], busy_by_device[busiest])
    gap_rows, rest = [], gaps
    for name in HOST_SPANS:
        covered = intersect(rest, union(host_spans[name]))
        gap_rows.append([name, total(covered) / 1e9])
        rest = subtract(rest, covered)
    gap_rows.append([UNATTRIBUTED, total(rest) / 1e9])

    busy_s = sum(total(b) for b in busy_by_device.values()) / 1e9 / chips
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "busy_s_by_device": {str(d): total(b) / 1e9
                             for d, b in busy_by_device.items()},
        "devices_with_operations": len(device_ops) or len(device_modules),
        "programs": programs,
        "collective_s": collective_s,
        "collective_calls": collective_calls,
        "host_span_counts": {n: len(v) for n, v in host_spans.items()},
        "longest_gap_s": max((b - a for a, b in gaps), default=0.0) / 1e9,
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": sorted(gap_rows, key=lambda r: -r[1])[:top],
        },
    }

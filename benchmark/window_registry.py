"""Window readings of the program's telemetry registry
(``telemetry.GLOBAL.snapshot()`` at the window's two ends, as
``RunView.registry_before`` / ``registry_after`` hold it).

The registry keeps sums and counts, no samples, so what a reader can take
from a histogram is a mean over the window: the delta of ``.sum`` over the
delta of ``.count``.  A key the program does not have (a program older than
the instrument) reads as None, and the harness leaves the metric out.
"""

from __future__ import annotations


def key(name: str, part: str = "", **labels) -> str:
    """The snapshot's key of one sample: ``name[.part]{k=v,...}``."""
    out = f"{name}.{part}" if part else name
    if labels:
        out += "{" + ",".join(f"{k}={v}" for k, v in labels.items()) + "}"
    return out


def delta(run, k: str):
    """Growth of a counter or of a histogram's sum or count inside the
    window; None where the registry has no such key."""
    if k not in run.registry_after:
        return None
    return run.registry_after[k] - run.registry_before.get(k, 0)


def over_labels(snapshot: dict, name: str, part: str = ""):
    """One snapshot's sum over every label set of a labelled family's
    ``part`` (``device_crossing_us.count{tag=...}`` over the tags); None
    where the registry holds no sample of the family."""
    prefix = key(name, part) + "{"
    found = [v for k, v in snapshot.items() if k.startswith(prefix)]
    return sum(found) if found else None


def delta_over_labels(run, name: str, part: str = ""):
    """``over_labels`` at the window's end less that at its start."""
    after = over_labels(run.registry_after, name, part)
    if after is None:
        return None
    return after - (over_labels(run.registry_before, name, part) or 0)


def ratio(num, den, scale: float = 1.0):
    if num is None or not den:
        return None
    return scale * num / den


def mean_ms(run, name: str, **labels):
    """Mean over the window of a microsecond histogram, in ms."""
    return ratio(delta(run, key(name, "sum", **labels)),
                 delta(run, key(name, "count", **labels)), 1e-3)


def round_phase_ms(run, phase: str):
    """Mean host time of an engine round's ``phase`` over the rounds the
    window's engines recorded (``tracing.RoundTimer``)."""
    return mean_ms(run, "engine_round_us", phase=phase)

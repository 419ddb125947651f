"""The every-tenth-round collection as ONE program: the fleet statistics
(core/fleet.py), the health triage (core/health.py) and the invariant probe
(core/invariants.py) over the resident state, leaving as one flat int32
vector, with the two per-group digests they carry between collections as
one ``[G, 17] int32`` array.

An engine used to run the three as three programs: three dispatches, the
``[G, K]`` sender ids handed over twice, 22 small arrays fetched and 17
``[G]`` digest arrays let go of, about fifty places where its thread gave the
interpreter lock up and had to take it back from the others (PERF.md
section 6, PR 38).  The mathematics is theirs and stays with them
(``_fleet_stats_impl``, ``_fleet_health_impl``, ``_check_invariants_impl``);
this module lays their results side by side at a static table of offsets
(``layout``), and ``decode`` hands the host's list of Python ints to their
own dict builders (``host_dict``), so ``last_fleet`` / ``last_health`` /
``last_invariants`` keep their keys and values.
"""

from __future__ import annotations

import functools
from math import prod

import jax
import jax.numpy as jnp

from dragonboat_tpu.core import fleet as _fleet
from dragonboat_tpu.core import health as _health
from dragonboat_tpu.core import invariants as _invariants
from dragonboat_tpu.core.health import HealthDigest
from dragonboat_tpu.core.invariants import InvariantDigest
from dragonboat_tpu.core.kstate import box_senders, unpack_state

#: the columns of the carried array: HealthDigest's ten, then
#: InvariantDigest's seven (each class's fields in its own order)
HEALTH_WIDTH = len(HealthDigest._fields)
CARRY_WIDTH = HEALTH_WIDTH + len(InvariantDigest._fields)
#: the invariant digest's age: the column an engine zeroes for a lane whose
#: occupant changed (step-scoped invariants never compare across occupants)
INV_TICKS_COL = HEALTH_WIDTH + InvariantDigest._fields.index("ticks")

#: the report fields that are no scalars (every other one is ``[]``)
_FLEET_SHAPES = {"role_count": (_fleet.NUM_ROLES,),
                 "lag_hist": (len(_fleet.LAG_BUCKETS) + 1,),
                 "inbox_hist": (len(_fleet.INBOX_BUCKETS) + 1,)}
_INVARIANT_SHAPES = {"per_invariant": (_invariants.NUM_INVARIANTS,)}


def _health_shapes(k: int) -> dict:
    return {"class_count": (_health.NUM_CLASSES,), "worst_idx": (k,),
            "worst_score": (k,), "worst_rows": (k, _health.ROW_WIDTH)}


@functools.lru_cache(maxsize=None)
def layout(num_lanes: int, k: int, probe: bool) -> tuple[tuple, int]:
    """Where each report lies in the packed vector of an engine of
    ``num_lanes`` lanes: ``((cls, ((field, start, shape), ...)), ...)`` and
    the vector's length.  ``FleetStats`` always; ``HealthReport`` where
    ``k`` > 0 (clamped to the lanes, as the triage clamps it);
    ``InvariantReport`` where ``probe``."""
    reports = [(_fleet.FleetStats, _FLEET_SHAPES)]
    if k > 0:
        reports.append((_health.HealthReport,
                        _health_shapes(min(int(k), num_lanes))))
    if probe:
        reports.append((_invariants.InvariantReport, _INVARIANT_SHAPES))
    at, table = 0, []
    for cls, shapes in reports:
        fields = []
        for name in cls._fields:
            shape = shapes.get(name, ())
            fields.append((name, at, shape))
            at += prod(shape)
        table.append((cls, tuple(fields)))
    return tuple(table), at


def empty_carry(num_lanes: int):
    """All-zero carried array (``ticks`` 0 marks every delta-based detector
    and every step-scoped invariant vacuous until the first collection)."""
    return jnp.zeros((num_lanes, CARRY_WIDTH), jnp.int32)


def split_carry(digest) -> tuple[HealthDigest, InvariantDigest]:
    """The carried ``[G, 17]`` array (device or host) as the two digests
    whose columns it holds."""
    cols = [digest[:, i] for i in range(CARRY_WIDTH)]
    return (HealthDigest(*cols[:HEALTH_WIDTH]),
            InvariantDigest(*cols[HEALTH_WIDTH:]))


def join_carry(health: HealthDigest, inv: InvariantDigest):
    return jnp.stack([*health, *inv], axis=1)


def _fleet_digest_impl(state, inbox_from, digest,
                       thresholds: _health.HealthThresholds
                       = _health.DEFAULT_THRESHOLDS,
                       k: int = _health.DEFAULT_TOP_K, probe: bool = True):
    """-> ``(vec, digest)``: the reports ``layout`` names, flattened into one
    int32 vector, and the carried array rewritten.  A part that is off
    (``k`` 0, ``probe`` False) is not traced and leaves its columns of
    ``digest`` as they are."""
    health, inv = split_carry(digest)
    reports = [_fleet._fleet_stats_impl(state, inbox_from)]
    if k > 0:
        report, health = _health._fleet_health_impl(
            state, inbox_from, health, thresholds, k)
        reports.append(report)
    if probe:
        report, inv = _invariants._check_invariants_impl(state, inv)
        reports.append(report)
    vec = jnp.concatenate([jnp.ravel(x).astype(jnp.int32)
                           for report in reports for x in report])
    return vec, join_carry(health, inv)


@functools.lru_cache(maxsize=None)
def digest_program(kp, thresholds, k: int, probe: bool, boxed: bool = False,
                   placement=None):
    """``_fleet_digest_impl`` as a jitted program ``(resident, inbox,
    digest) -> (vec, digest)`` on an engine's resident form: one per
    ``(kp, thresholds, k, which parts are on)`` and backend.  ``boxed``:
    ``inbox`` is the mesh backend's carried ``[G, Wi]`` inbox, whose sender
    ids are sliced out in here (the serial backend uploads its ``[G, K]``
    host array).  The carried array comes back placed as ``placement`` says
    (sharded along G like the state it derives from); the vector is every
    device's."""
    def run(resident, inbox, digest):
        return _fleet_digest_impl(
            unpack_state(kp, resident),
            box_senders(kp, inbox) if boxed else inbox,
            digest, thresholds, k, probe)

    return jax.jit(run, out_shardings=(None, placement))


@functools.lru_cache(maxsize=None)
def carry_view_program(placement=None):
    """``split_carry`` jitted, for readers outside a round (a lane's health
    row, the chaos oracle, tests): 17 ``[G]`` arrays, placed as the carried
    array is."""
    return jax.jit(split_carry, out_shardings=placement)


def decode(ints: list, num_lanes: int, k: int, probe: bool) -> list:
    """The packed vector, as ONE ``tolist`` gave it, -> ``[fleet, health,
    invariants]``: each report's dict as its own module's ``host_dict``
    builds it from Python ints (the dicts ``stats_to_dict`` /
    ``report_to_dict`` give), None for a part that is off (``k`` 0, no
    ``probe``)."""
    table, total = layout(num_lanes, k, probe)
    if len(ints) != total:
        raise ValueError(f"packed digest holds {len(ints)} ints, its "
                         f"layout {total}")
    dicts = dict.fromkeys(_HOST_DICT)
    for cls, fields in table:
        values = {}
        for name, at, shape in fields:
            if not shape:
                values[name] = ints[at]
            elif len(shape) == 1:
                values[name] = ints[at:at + shape[0]]
            else:
                rows, width = shape
                values[name] = [ints[at + r * width:at + (r + 1) * width]
                                for r in range(rows)]
        dicts[cls] = _HOST_DICT[cls](cls(**values))
    return list(dicts.values())


_HOST_DICT = {_fleet.FleetStats: _fleet.host_dict,
              _health.HealthReport: _health.host_dict,
              _invariants.InvariantReport: _invariants.host_dict}

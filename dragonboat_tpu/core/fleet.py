"""Device-side fleet telemetry reduction over the batched ShardState.

At 10^4–10^5 lanes, "how many shards are leaderless right now" must not
be answered by iterating shards on host — one vectorized reduction over
the resident ``ShardState`` produces a single small ``FleetStats``
struct, and a decimation knob on the engines (``fleet_stats_every``)
bounds the host transfer to one every N steps: the struct's 11 fields
ride the flat int32 vector of the engines' one collection program
(core/digest.py), beside the health and invariant reports.

``fleet_stats`` is jitted and tracer-safe (pure jnp ops, no Python
branching on traced values); the host-side helpers below turn a fetched
struct into plain dicts and register callback gauges on a
``telemetry.Registry`` so the /metrics endpoint exposes
``fleet_role_count{role=...}`` and the cumulative lag / inbox-occupancy
bucket families.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from dragonboat_tpu.core import params as P

NUM_ROLES = 6
# index == the params.py role constant (FOLLOWER=0 .. WITNESS=5)
ROLE_NAMES = ("follower", "candidate", "pre_vote_candidate", "leader",
              "non_voting", "witness")

# cumulative `le` bounds; the +Inf bucket is implicit (== occupied)
LAG_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64)
INBOX_BUCKETS = (0, 1, 2, 4, 8)

# Partition contract for the stats struct (grammar: core/kstate.py
# CONTRACTS; checked by analysis/partition.py).  Every field is an
# aggregate over ALL groups: replicated on every device, and produced by
# an intentional cross-G collective — `collective=declared` licenses the
# cross-G reductions inside _fleet_stats_impl that the partition pass
# would otherwise flag as PS001.  Axis names: ROLES == NUM_ROLES,
# LAGB/INBOXB == len(*_BUCKETS)+1 (host-side constants, not kernel
# geometry — the shape side of this table is documentation, the
# part/collective side is machine-checked).
CONTRACTS = {
    "FleetStats": {
        "occupied": "[] i32 part=replicated collective=declared",
        "role_count": "[ROLES] i32 part=replicated collective=declared",
        "leaderless": "[] i32 part=replicated collective=declared",
        "election_active": "[] i32 part=replicated collective=declared",
        "quiesced": "[] i32 part=replicated collective=declared",
        "quiesced_by_word": "[] i32 part=replicated collective=declared",
        "quiesce_wakes": "[] i32 part=replicated collective=declared",
        "term_max": "[] i32 part=replicated collective=declared",
        "term_min": "[] i32 part=replicated collective=declared",
        "lag_hist": "[LAGB] i32 part=replicated collective=declared",
        "inbox_hist": "[INBOXB] i32 part=replicated collective=declared",
    },
}


def bucket_labels(bounds) -> tuple:
    return tuple(str(b) for b in bounds) + ("+Inf",)


class FleetStats(NamedTuple):
    """The fleet telemetry of one collection (all i32): the first block of
    the one vector an engine fetches (core/digest.py ``layout``)."""

    occupied: jnp.ndarray         # [] — lanes with >= 1 configured peer
    role_count: jnp.ndarray       # [NUM_ROLES]
    leaderless: jnp.ndarray       # [] — occupied lanes with no known leader
    election_active: jnp.ndarray  # [] — candidates + pre-vote candidates
    quiesced: jnp.ndarray         # [] — occupied lanes masked-quiesced
    quiesced_by_word: jnp.ndarray  # [] — of them, entered on a peer's word
    quiesce_wakes: jnp.ndarray    # [] — quiesce_epoch over occupied lanes
    term_max: jnp.ndarray         # [] (0 when no lane is occupied)
    term_min: jnp.ndarray         # [] (0 when no lane is occupied)
    lag_hist: jnp.ndarray         # [len(LAG_BUCKETS)+1] cumulative counts
    inbox_hist: jnp.ndarray       # [len(INBOX_BUCKETS)+1] cumulative


def _fleet_stats_impl(state, inbox_from) -> FleetStats:
    i32 = jnp.int32
    occ = (state.kind != P.K_ABSENT).any(axis=1)              # [G] bool
    occ_i = occ.astype(i32)
    occupied = occ_i.sum()
    roles = jnp.arange(NUM_ROLES, dtype=state.role.dtype)
    role_count = (occ_i[:, None]
                  * (state.role[:, None] == roles[None, :]).astype(i32)
                  ).sum(axis=0)
    leaderless = (occ & (state.leader == P.NO_LEADER)).astype(i32).sum()
    election_active = (occ & ((state.role == P.CANDIDATE)
                              | (state.role == P.PRE_VOTE_CANDIDATE))
                       ).astype(i32).sum()
    quiesced = (occ & state.quiesced).astype(i32).sum()
    # entry leaves idle_tick where it found it: at the threshold for a lane
    # that crossed on its own clock, under it for one that followed a peer
    # (core/kernel.py 5b)
    quiesced_by_word = (occ & state.quiesced
                        & (state.idle_tick < state.e_timeout * 10)
                        ).astype(i32).sum()
    # monotone while the occupants stay: a lane's wakes so far, summed
    quiesce_wakes = jnp.where(occ, state.quiesce_epoch, 0).sum()
    big = jnp.iinfo(jnp.int32).max
    term_max = jnp.where(occ, state.term, 0).max()
    term_min = jnp.where(occupied > 0,
                         jnp.where(occ, state.term, big).min(), 0)
    lag = state.committed - state.applied                     # [G] i32
    bounds = jnp.asarray(LAG_BUCKETS, i32)
    lag_le = ((lag[:, None] <= bounds[None, :])
              & occ[:, None]).astype(i32).sum(axis=0)
    lag_hist = jnp.concatenate([lag_le, occupied[None]])
    inbox_occ = (inbox_from != 0).astype(i32).sum(axis=1)     # [G]
    ibounds = jnp.asarray(INBOX_BUCKETS, i32)
    inbox_le = ((inbox_occ[:, None] <= ibounds[None, :])
                & occ[:, None]).astype(i32).sum(axis=0)
    inbox_hist = jnp.concatenate([inbox_le, occupied[None]])
    return FleetStats(
        occupied=occupied, role_count=role_count, leaderless=leaderless,
        election_active=election_active, quiesced=quiesced,
        quiesced_by_word=quiesced_by_word, quiesce_wakes=quiesce_wakes,
        term_max=term_max,
        term_min=term_min, lag_hist=lag_hist, inbox_hist=inbox_hist)


fleet_stats = jax.jit(_fleet_stats_impl)


def stats_to_dict(stats: FleetStats) -> dict:
    """Fetch to host and flatten into plain ints/dicts — the shape the
    callback gauges (and ``engine.last_fleet``) serve."""
    return host_dict(jax.device_get(stats))


def host_dict(s: FleetStats) -> dict:
    """``stats_to_dict`` of a struct already on the host: numpy values, or
    the Python ints and lists the engines decode from their packed digest
    (core/digest.py)."""
    lag_labels = bucket_labels(LAG_BUCKETS)
    inbox_labels = bucket_labels(INBOX_BUCKETS)
    return {
        "occupied": int(s.occupied),
        "role_count": {ROLE_NAMES[i]: int(s.role_count[i])
                       for i in range(NUM_ROLES)},
        "leaderless": int(s.leaderless),
        "election_active": int(s.election_active),
        "quiesced": int(s.quiesced),
        "quiesced_by_word": int(s.quiesced_by_word),
        "quiesce_wakes": int(s.quiesce_wakes),
        "term_max": int(s.term_max),
        "term_min": int(s.term_min),
        "lag_hist": {lab: int(s.lag_hist[i])
                     for i, lab in enumerate(lag_labels)},
        "inbox_hist": {lab: int(s.inbox_hist[i])
                       for i, lab in enumerate(inbox_labels)},
    }


def empty_dict() -> dict:
    """All-zero fleet dict (merge identity for hosts with no engine)."""
    return {
        "occupied": 0,
        "role_count": {r: 0 for r in ROLE_NAMES},
        "leaderless": 0,
        "election_active": 0,
        "quiesced": 0,
        "term_max": 0,
        "term_min": 0,
        "lag_hist": {lab: 0 for lab in bucket_labels(LAG_BUCKETS)},
        "inbox_hist": {lab: 0 for lab in bucket_labels(INBOX_BUCKETS)},
    }


def merge_into(base: dict, other: dict) -> None:
    """Accumulate ``other`` (same shape as ``empty_dict``) into
    ``base``: counts add, term_max maxes, term_min mins over nonzero."""
    base["occupied"] += other["occupied"]
    base["leaderless"] += other["leaderless"]
    base["election_active"] += other["election_active"]
    base["quiesced"] += other.get("quiesced", 0)
    base["term_max"] = max(base["term_max"], other["term_max"])
    mins = [m for m in (base["term_min"], other["term_min"]) if m > 0]
    base["term_min"] = min(mins) if mins else 0
    for k in base["role_count"]:
        base["role_count"][k] += other["role_count"].get(k, 0)
    for k in base["lag_hist"]:
        base["lag_hist"][k] += other["lag_hist"].get(k, 0)
    for k in base["inbox_hist"]:
        base["inbox_hist"][k] += other["inbox_hist"].get(k, 0)


def add_host_shard(base: dict, role: str, leaderless: bool, term: int,
                   lag: int, quiesced: bool = False) -> None:
    """Fold one HOST-resident (non-kernel) replica into a fleet dict —
    host clusters have no device state to reduce, but the /metrics
    surface must still answer role/leaderless/lag questions."""
    base["occupied"] += 1
    if role in base["role_count"]:
        base["role_count"][role] += 1
    if leaderless:
        base["leaderless"] += 1
    if role in ("candidate", "pre_vote_candidate"):
        base["election_active"] += 1
    if quiesced:
        base["quiesced"] += 1
    if term > 0:
        base["term_max"] = max(base["term_max"], term)
        base["term_min"] = (term if base["term_min"] == 0
                            else min(base["term_min"], term))
    for bound in LAG_BUCKETS:
        if lag <= bound:
            base["lag_hist"][str(bound)] += 1
    base["lag_hist"]["+Inf"] += 1
    # a host replica's inbox is the Python queue, drained every step:
    # occupancy 0 lands in every cumulative bucket
    for bound in INBOX_BUCKETS:
        base["inbox_hist"][str(bound)] += 1
    base["inbox_hist"]["+Inf"] += 1


def register_exposition(registry, source, replace: bool = False) -> None:
    """Register the fleet callback-gauge families on ``registry``,
    backed by ``source()`` -> fleet dict (or None for "no data yet").

    Idempotent when ``replace`` is False: an already-registered family
    set (e.g. the owning NodeHost's merged view) is left alone, so a
    standalone engine can offer its device-only view without fighting a
    host that registered first.  ``replace=True`` re-points the
    callbacks (host restart)."""
    if not replace and registry.kind_of("fleet_role_count") is not None:
        return

    def _get() -> dict:
        d = source()
        return d if d is not None else empty_dict()

    registry.gauge_fn(
        "fleet_role_count",
        lambda: {(r,): _get()["role_count"][r] for r in ROLE_NAMES},
        help="occupied shards per raft role", labelnames=("role",))
    registry.gauge_fn("fleet.occupied_shards",
                      lambda: _get()["occupied"],
                      help="lanes with at least one configured peer")
    registry.gauge_fn("fleet.leaderless_shards",
                      lambda: _get()["leaderless"],
                      help="occupied shards with no known leader")
    registry.gauge_fn("fleet.election_active",
                      lambda: _get()["election_active"],
                      help="shards currently campaigning")
    registry.gauge_fn("fleet.quiesced_shards",
                      lambda: _get().get("quiesced", 0),
                      help="occupied shards in masked quiesce")
    registry.gauge_fn("fleet.term_max", lambda: _get()["term_max"],
                      help="max raft term over occupied shards")
    registry.gauge_fn("fleet.term_min", lambda: _get()["term_min"],
                      help="min raft term over occupied shards")
    registry.gauge_fn(
        "fleet_commit_lag_bucket",
        lambda: {(lab,): _get()["lag_hist"][lab]
                 for lab in bucket_labels(LAG_BUCKETS)},
        help="cumulative commit-applied lag distribution",
        labelnames=("le",))
    registry.gauge_fn(
        "fleet_inbox_occupancy_bucket",
        lambda: {(lab,): _get()["inbox_hist"][lab]
                 for lab in bucket_labels(INBOX_BUCKETS)},
        help="cumulative inbox slot occupancy distribution",
        labelnames=("le",))

"""Static kernel geometry + the shared counter-based PRNG.

The batched kernel is compiled for a fixed geometry: G shards × P peer slots,
a CAP-entry term ring, K inbox slots, B proposal slots and an RI-slot
ReadIndex book per shard.  All lanes are int32: JAX's default integer width —
terms/indexes are per-shard logical clocks that a shard would take years to
overflow at raft rates, and the host records full-width u64 in raftpb.

The randomized election timeout uses a splitmix32-style counter hash keyed by
(shard seed, reset counter) so device and host cores draw identical values —
this keeps the pycore differential oracle in exact lockstep
(reference behavior: raft.go:658 setRandomizedElectionTimeout draws
uniform [electionTimeout, 2*electionTimeout)).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class KernelParams:
    num_peers: int = 3          # P: peer slots per shard (max replicas)
    log_cap: int = 1024         # CAP: term-ring capacity (power of two)
    inbox_cap: int = 8          # K: inbound messages per shard per step
    msg_entries: int = 8        # E: max entries carried per replicate message
    proposal_cap: int = 8       # B: proposals per shard per step
    readindex_cap: int = 8      # RI: pending ReadIndex contexts per shard
    apply_batch: int = 64       # max committed entries released per step
    compaction_overhead: int = 64  # retained entries below the compact floor
    # inline payload lanes (lv ring + ent_val routing) for device-resident
    # RSMs; off by default — host-side-payload deployments skip the cost
    inline_payloads: bool = False
    # (merge_inbox_families, a hand-restructured unrolled pass over the
    # ring-invariant families, lived here r2-r4; it measured slower on
    # BOTH platforms — 28x on XLA:CPU, +40% on TPU v5e — so it was
    # removed in r5.  Reviving it would need a new hypothesis for why a
    # materialized-buffer chain could beat the aliased scan carry.)
    # read dynamically-indexed state (the [log_cap] rings, the [P] peer
    # books, the [RI] read book, the router's [K]/[R] lanes) by one-hot
    # select instead of dynamic indexing.  On TPU the batched gather
    # that vmapped indexing lowers to serializes over the [G] axis (r4
    # ladder: ~0.32 ms/group of linear step cost against a ~10 µs
    # roofline); the one-hot form is wide VPU passes.  On XLA:CPU the
    # gather is a real O(1) load and the one-hot form costs 1.4-3.5x
    # step time (rings worst).  Default False (the CPU graph — also what
    # direct constructors in tests get); the real entry points
    # (bench_loop.bench_params, NodeHost._kernel_params) flip it on
    # whenever the backend is not cpu.  Bitwise-identical either way
    # (differential-tested).
    onehot_reads: bool = False
    # unroll the per-family inbox scans (lax.scan unroll flag — bitwise
    # neutral, pure scheduling).  Off everywhere by default: XLA:CPU
    # measured 11x slower unrolled (the rolled carry aliases in place).
    # Exists for the TPU A/B, where each rolled iteration is its own
    # serial launch of the full family body.
    unroll_scans: bool = False
    # S: ring entries per lane that ride the round's packed download (the
    # save window's terms, kstate.round_columns).  0 derives it from what
    # one step can append; a smaller power of two is for tests of the
    # engine's whole-row fallback
    save_window: int = 0

    def __post_init__(self) -> None:
        assert self.log_cap & (self.log_cap - 1) == 0, "log_cap must be 2^n"
        assert self.readindex_cap & (self.readindex_cap - 1) == 0
        assert self.save_window & (self.save_window - 1) == 0 \
            and self.save_window <= self.log_cap, "save_window must be 2^n"


def slot_families(K: int) -> tuple[str, ...]:
    """Static per-slot message families for the kernel inbox.

    The device router's slot layout (router.py) is typed: per remote peer,
    two response lanes, a replicate lane, a heartbeat lane and a
    vote/TimeoutNow lane.  Exposing that statically lets the kernel scan
    each family with a body containing ONLY that family's handlers —
    the dispatch-by-type restructuring that removes most of the serial
    inbox-scan cost (PERF.md lever #1).  Slots beyond whole 5-slot units
    are 'any': they accept every type and run the full handler body
    (hosts staging arbitrary network traffic use these).

    resp: *_RESP, NOOP, UNREACHABLE, SNAPSHOT_STATUS
    rep:  REPLICATE      hb: HEARTBEAT
    vote: REQUEST_VOTE, REQUEST_PREVOTE, TIMEOUT_NOW
    """
    u = K // 5
    return ("resp", "resp", "rep", "hb", "vote") * u + ("any",) * (K - 5 * u)


# role encoding — parity with pycore.RaftState / raft.go:63-71
FOLLOWER = 0
CANDIDATE = 1
PRE_VOTE_CANDIDATE = 2
LEADER = 3
NON_VOTING = 4
WITNESS = 5

# peer-slot kinds
K_ABSENT = 0
K_VOTER = 1
K_NON_VOTING = 2
K_WITNESS = 3

# remote flow-control states — parity remote.go:52-70
R_RETRY = 0
R_WAIT = 1
R_REPLICATE = 2
R_SNAPSHOT = 3

NO_LEADER = 0

# What a heartbeat lane's commit reads when the lane carries not a
# heartbeat but the word of a replica that entered quiesce on its own
# idle clock (upstream's Quiesce message, node.go sendEnterQuiesceMessages):
# a real heartbeat's commit is never negative.  The host spells the lane
# MT.QUIESCE on the wire (engine/kernel_engine.py), the device router in
# the inbox (core/router.py), and the kernel reads it there (step 0b).
QUIESCE_WORD = -1


import numpy as np

_U = np.uint32


def splitmix32(x):
    """Deterministic 32-bit mixer usable from numpy scalars and jnp arrays.

    Callers pass uint32-typed values; constants are np.uint32 so JAX's weak
    typing doesn't reject them and numpy wraps mod 2^32."""
    if isinstance(x, (int, np.integer)):
        # host flavor: plain python ints, wrap mod 2^32
        m = 0xFFFFFFFF
        x = (int(x) + 0x9E3779B9) & m
        z = ((x ^ (x >> 16)) * 0x85EBCA6B) & m
        z = ((z ^ (z >> 13)) * 0xC2B2AE35) & m
        return _U(z ^ (z >> 16))
    x = x + _U(0x9E3779B9)
    z = (x ^ (x >> _U(16))) * _U(0x85EBCA6B)
    z = (z ^ (z >> _U(13))) * _U(0xC2B2AE35)
    return z ^ (z >> _U(16))


def randomized_timeout(seed: int, counter: int, election_timeout: int) -> int:
    """election_timeout + uniform-ish [0, election_timeout) — host flavor,
    bit-identical to the kernel's _next_rand_timeout draw."""
    mixed = splitmix32((seed & 0xFFFFFFFF) ^ (((counter & 0xFFFFFFFF) * 0x632BE5AB) & 0xFFFFFFFF))
    return election_timeout + int(mixed) % election_timeout

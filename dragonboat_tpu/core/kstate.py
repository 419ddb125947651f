"""SoA device state for the batched Raft kernel.

The reference keeps per-shard state in a ``raft`` struct of maps and slices
(``internal/raft/raft.go:199-239``); here the same information is a
structure-of-arrays pytree with a leading ``[G]`` shard axis so one vmapped
step advances every shard in lockstep (BASELINE.json north star).  Peer books
are fixed ``[G, P]`` lanes (the reference's ``remote`` is already fixed-width:
remote.go:72), the entry log is a ``[G, CAP]`` term ring (payloads live
host-side or in the device RSM's value lanes), and the ReadIndex book is a
``[G, RI]`` circular queue.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dragonboat_tpu.core import params as P

# ---------------------------------------------------------------------------
# Machine-readable field contracts (checked by analysis/contracts.py).
#
# Grammar, one string per field:
#
#   "[<axes>] <dtype> [tag ...]"
#
#   axes    comma-separated symbolic axis names over the kernel geometry:
#           G  shard axis               (num_shards — vmap strips it)
#           P  peer slots               (KernelParams.num_peers)
#           CAP  term-ring capacity     (KernelParams.log_cap, power of two)
#           K  inbox slots              (KernelParams.inbox_cap)
#           E  entries per message      (KernelParams.msg_entries)
#           B  proposal slots           (KernelParams.proposal_cap)
#           RI ReadIndex book slots     (KernelParams.readindex_cap, 2^n)
#   dtype   i32 | bool
#   tags    ring            the leading non-G axis is a power-of-two ring:
#                           dynamic indexing into it must be masked with
#                           `& (cap - 1)` (or argmax/arange-bounded to it)
#           domain=A..B     values live in [params.A, params.B] inclusive
#           optional        field is None unless the config materializes it
#           part=G          the field carries PER-GROUP data: at the mesh
#                           level its leading G axis is sharded over the
#                           ('g','r') device mesh (parallel/ici.py) and no
#                           kernel code may reduce/gather across G outside
#                           a declared collective (analysis/partition.py)
#           part=replicated the field is identical on every device (e.g.
#                           fleet-stats aggregates); mixing it into
#                           G-sharded math needs an explicit broadcast
#           collective=declared
#                           the struct's fields are produced by an
#                           INTENTIONAL cross-G collective (core/fleet.py
#                           FleetStats); cross-G reductions inside the
#                           producing function are by design
#
# The contracts pass (scripts/lint.py --pass contracts) parses this dict
# from the AST (it must stay a literal), abstractly interprets
# core/kernel.py against it, and cross-validates it against the
# eval-shaped structures built by init_state/empty_inbox/empty_input and
# the step output.  Editing a field here without updating the arrays (or
# vice versa) is a lint failure, not a comment drifting out of date.
# ---------------------------------------------------------------------------

CONTRACTS = {
    "ShardState": {
        # identity / config
        "replica_id": "[G] i32 part=G",
        "seed": "[G] i32 part=G",
        "e_timeout": "[G] i32 part=G",
        "h_timeout": "[G] i32 part=G",
        "check_quorum": "[G] bool part=G",
        "pre_vote": "[G] bool part=G",
        # core protocol state
        "role": "[G] i32 domain=FOLLOWER..WITNESS part=G",
        "term": "[G] i32 part=G",
        "vote": "[G] i32 part=G",
        "leader": "[G] i32 part=G",
        "applied": "[G] i32 part=G",
        "e_tick": "[G] i32 part=G",
        "h_tick": "[G] i32 part=G",
        "rand_timeout": "[G] i32 part=G",
        "rand_counter": "[G] i32 part=G",
        "pending_cc": "[G] bool part=G",
        "ltt": "[G] i32 part=G",
        "is_ltt": "[G] bool part=G",
        # peer books
        "pid": "[G, P] i32 part=G",
        "kind": "[G, P] i32 domain=K_ABSENT..K_WITNESS part=G",
        "match": "[G, P] i32 part=G",
        "next": "[G, P] i32 part=G",
        "pstate": "[G, P] i32 domain=R_RETRY..R_SNAPSHOT part=G",
        "active": "[G, P] bool part=G",
        "psnap": "[G, P] i32 part=G",
        "vresp": "[G, P] bool part=G",
        "vgrant": "[G, P] bool part=G",
        # log ring + cursors
        "lt": "[G, CAP] i32 ring part=G",
        "lcc": "[G, CAP] bool ring part=G",
        "snap_index": "[G] i32 part=G",
        "snap_term": "[G] i32 part=G",
        "last": "[G] i32 part=G",
        "committed": "[G] i32 part=G",
        "processed": "[G] i32 part=G",
        "stable": "[G] i32 part=G",
        # ReadIndex circular book
        "ri_low": "[G, RI] i32 ring part=G",
        "ri_high": "[G, RI] i32 ring part=G",
        "ri_index": "[G, RI] i32 ring part=G",
        "ri_acks": "[G, RI, P] bool ring part=G",
        "ri_head": "[G] i32 part=G",
        "ri_count": "[G] i32 part=G",
        "needs_host": "[G] bool part=G",
        # device quiesce (the kernel-masked form of quiesce.py)
        "quiesce_on": "[G] bool part=G",
        "idle_tick": "[G] i32 part=G",
        "quiesced": "[G] bool part=G",
        "quiesce_epoch": "[G] i32 part=G",
        "lv": "[G, CAP] i32 ring optional part=G",
    },
    "Inbox": {
        "mtype": "[G, K] i32 part=G",
        "from_": "[G, K] i32 part=G",
        "term": "[G, K] i32 part=G",
        "log_term": "[G, K] i32 part=G",
        "log_index": "[G, K] i32 part=G",
        "commit": "[G, K] i32 part=G",
        "reject": "[G, K] bool part=G",
        "hint": "[G, K] i32 part=G",
        "hint_high": "[G, K] i32 part=G",
        "n_ent": "[G, K] i32 part=G",
        "ent_term": "[G, K, E] i32 part=G",
        "ent_cc": "[G, K, E] bool part=G",
        "ent_val": "[G, K, E] i32 optional part=G",
    },
    "StepInput": {
        "prop_valid": "[G, B] bool part=G",
        "prop_cc": "[G, B] bool part=G",
        "ri_valid": "[G] bool part=G",
        "ri_low": "[G] i32 part=G",
        "ri_high": "[G] i32 part=G",
        "transfer_to": "[G] i32 part=G",
        "tick": "[G] bool part=G",
        "quiesced": "[G] bool part=G",
        "applied": "[G] i32 part=G",
        "prop_val": "[G, B] i32 optional part=G",
    },
    "StepOutput": {
        "r_type": "[G, K] i32 part=G",
        "r_to": "[G, K] i32 part=G",
        "r_term": "[G, K] i32 part=G",
        "r_log_index": "[G, K] i32 part=G",
        "r_reject": "[G, K] bool part=G",
        "r_hint": "[G, K] i32 part=G",
        "r_hint_high": "[G, K] i32 part=G",
        "s_rep": "[G, P] bool part=G",
        "s_prev_index": "[G, P] i32 part=G",
        "s_prev_term": "[G, P] i32 part=G",
        "s_commit": "[G, P] i32 part=G",
        "s_n_ent": "[G, P] i32 part=G",
        "s_ent_term": "[G, P, E] i32 part=G",
        "s_ent_cc": "[G, P, E] bool part=G",
        "s_ent_val": "[G, P, E] i32 optional part=G",
        "s_vote": "[G, P] i32 part=G",
        "s_vote_term": "[G, P] i32 part=G",
        "s_vote_lindex": "[G, P] i32 part=G",
        "s_vote_lterm": "[G, P] i32 part=G",
        "s_vote_hint": "[G, P] i32 part=G",
        "s_hb": "[G, P] bool part=G",
        "s_hb_commit": "[G, P] i32 part=G",
        "s_hb_low": "[G, P] i32 part=G",
        "s_hb_high": "[G, P] i32 part=G",
        "s_timeout_now": "[G, P] bool part=G",
        "s_need_snapshot": "[G, P] bool part=G",
        "s_wit_snap": "[G, P] bool part=G",
        "save_first": "[G] i32 part=G",
        "save_last": "[G] i32 part=G",
        "apply_first": "[G] i32 part=G",
        "apply_last": "[G] i32 part=G",
        "term": "[G] i32 part=G",
        "vote": "[G] i32 part=G",
        "commit": "[G] i32 part=G",
        "rtr_valid": "[G, RI] bool part=G",
        "rtr_index": "[G, RI] i32 part=G",
        "rtr_low": "[G, RI] i32 part=G",
        "rtr_high": "[G, RI] i32 part=G",
        "ri_dropped": "[G] bool part=G",
        "prop_accepted": "[G, B] bool part=G",
        "prop_index": "[G, B] i32 part=G",
        "prop_term": "[G, B] i32 part=G",
        "leader": "[G] i32 part=G",
        "leader_term": "[G] i32 part=G",
        "needs_host": "[G] bool part=G",
    },
}


# ---------------------------------------------------------------------------
# Protocol invariants (grammar: analysis/common.py parse_invariant).
#
# Machine-readable cross-field per-group invariants over ShardState —
# the Raft safety conditions the vectorized kernel must uphold, in a form
# all three verifier legs consume:
#
#   * analysis/safety.py statically checks every kernel store to a
#     participating field against these (RS001–RS006),
#   * scripts/model_check.py asserts them at every state of the
#     exhaustively explored small scope,
#   * core/invariants.py evaluates them as a jitted [G] reduction on the
#     live fleet (the runtime probe).
#
# STATE-scoped invariants hold of any single observation; ``prev.`` terms
# make an invariant STEP-scoped — it constrains a transition (for the
# runtime probe, a transition between two decimated observations, which is
# sound for the monotone/guarded forms below).  Deliberately absent:
# ``stable`` (legitimately lowered when a replicate truncates an unstable
# suffix) and the snapshot cursors (host-mediated injection moves them
# non-monotonically by design).
#
# Like CONTRACTS this must stay a pure literal (ast.literal_eval).
# ---------------------------------------------------------------------------

INVARIANTS = {
    # the commit cursor can never pass the end of the log
    "commit_within_log": "committed <= last",
    # entries are released to the apply pipeline only once committed
    "processed_within_commit": "processed <= committed",
    # the RSM-confirmed cursor can never pass what was released to it
    "applied_within_processed": "applied <= processed",
    # terms are monotonically non-decreasing
    "term_monotone": "term >= prev.term",
    # the commit cursor is monotonically non-decreasing
    "commit_monotone": "committed >= prev.committed",
    # at most one vote per term: while the term holds still, a cast vote
    # (nonzero) never changes
    "vote_once_per_term":
        "term == prev.term & prev.vote != 0 => vote == prev.vote",
    # a stable leader advances commit only to quorum-matched indexes.
    # Guarded on prev.role == LEADER & term == prev.term: a freshly
    # elected leader's peer match book resets to 0 while its commit
    # cursor (inherited as follower) may already be ahead — only commit
    # ADVANCES under stable same-term leadership must be quorum-backed.
    "leader_commit_quorum":
        "role == LEADER & prev.role == LEADER & term == prev.term"
        " & committed > prev.committed => quorum(match) >= committed",
    # a quiesced replica never campaigns (no term movement) or grants
    # votes.  quiesce_epoch bumps on every wake, so an unchanged epoch
    # between two observations proves the lane stayed quiesced for the
    # WHOLE interval — making both forms sound at any probe decimation
    # (a wake + re-quiesce between observations changes the epoch and
    # the guard fails vacuously)
    "quiesced_no_campaign":
        "prev.quiesced == 1 & quiesced == 1"
        " & quiesce_epoch == prev.quiesce_epoch => term == prev.term",
    "quiesced_no_vote":
        "prev.quiesced == 1 & quiesced == 1"
        " & quiesce_epoch == prev.quiesce_epoch => vote == prev.vote",
}


# ---------------------------------------------------------------------------
# Buffer-donation contract (checked by analysis/contracts.py, KC008).
#
# Each entry names a jitted entry point that donates argument buffers to
# XLA and records WHICH positional arguments (and the parameter names
# they bind) are donated.  Entries default to core/kernel.py; an entry
# with a ``module`` key declares a donating entry elsewhere (the mesh
# serve step in parallel/ici.py, the router differential twin).  The
# analyzer parses each module's decorators and fails lint if the
# ``donate_argnums`` there drifts from this declaration — so the
# host-side rule below is always describing the real kernel, not a
# stale comment.
#
# Host rule implied by donation: after dispatching a donated entry point
# the caller MUST NOT read or re-pass the donated argument arrays — XLA
# may have reused their memory for the outputs.  All host reads go
# through the returned state/output (or host mirrors); the engine's
# staging re-materializes a fresh upload every step.
# Backends that cannot donate (CPU) silently copy instead; the engine
# keeps the same discipline regardless so behavior is backend-uniform.
# ---------------------------------------------------------------------------

DONATION = {
    "step_donated": {
        "argnums": (1, 2, 3),
        "params": ("state", "inbox", "inp"),
        # partition identity of the donation (analysis/partition.py,
        # PS004): XLA reuses donor memory for results, which is only
        # sound if donor and result live under the SAME sharding.  Every
        # donor class must share its declared partition with at least one
        # result class.
        "donor_classes": ("ShardState", "Inbox", "StepInput"),
        "result_classes": ("ShardState", "StepOutput"),
    },
    "serve_step_donated": {
        # the mesh dispatch entry: state, the carried device inbox and
        # the staged input are donated; the partition mask (argnum 5) is
        # cached across steps by the engine and must NOT be donated
        "module": "dragonboat_tpu/parallel/ici.py",
        "function": "jit_serve_step_donated",
        "argnums": (2, 3, 4),
        "params": ("state", "box", "inp"),
        "donor_classes": ("ShardState", "Inbox", "StepInput"),
        "result_classes": ("ShardState", "Inbox", "StepOutput"),
    },
    "round_step_donated": {
        # the serial engine round (resident state and packed upload in,
        # resident state and packed download out): only the state is
        # donated, in its resident form (ResidentState below: the packed
        # columns and the rings, each the shape of its own result) — the
        # upload matches no output's shape, the download is an output
        "module": "dragonboat_tpu/core/round.py",
        "function": "step_donated",
        "argnums": (2,),
        "params": ("state",),
        "donor_classes": ("ShardState",),
        "result_classes": ("ShardState",),
    },
    "round_serve_step_donated": {
        # the mesh engine round: the resident state and the carried
        # device inbox (one [G, Wi] array) are donated; the packed upload
        # and the cached partition mask are not
        "module": "dragonboat_tpu/parallel/round.py",
        "function": "jit_serve_step_donated",
        "argnums": (2, 3),
        "params": ("state", "box"),
        "donor_classes": ("ShardState", "Inbox"),
        "result_classes": ("ShardState", "Inbox"),
    },
    "cluster_step_donated": {
        # router-layout twin used by the depth-1 differential arm: same
        # donation triple as step_donated, fused with device routing
        "module": "dragonboat_tpu/core/router.py",
        "argnums": (2, 3, 4),
        "params": ("state", "inbox", "inp"),
        "donor_classes": ("ShardState", "Inbox", "StepInput"),
        "result_classes": ("ShardState", "Inbox", "StepOutput"),
    },
}


class ShardState(NamedTuple):
    """Per-shard raft state; every field has a leading [G] axis (or [G, ...])."""

    # identity / config
    replica_id: jnp.ndarray     # [G] i32 — local replica id within the shard
    seed: jnp.ndarray           # [G] i32 — PRNG stream id
    e_timeout: jnp.ndarray      # [G] i32 — election timeout in ticks
    h_timeout: jnp.ndarray      # [G] i32 — heartbeat timeout in ticks
    check_quorum: jnp.ndarray   # [G] bool
    pre_vote: jnp.ndarray       # [G] bool

    # core protocol state
    role: jnp.ndarray           # [G] i32 ∈ {FOLLOWER..WITNESS}
    term: jnp.ndarray           # [G] i32
    vote: jnp.ndarray           # [G] i32 (replica id, 0 = none)
    leader: jnp.ndarray         # [G] i32 (0 = NoLeader)
    applied: jnp.ndarray        # [G] i32 — RSM-confirmed applied index
    e_tick: jnp.ndarray         # [G] i32
    h_tick: jnp.ndarray         # [G] i32
    rand_timeout: jnp.ndarray   # [G] i32
    rand_counter: jnp.ndarray   # [G] i32 — bumps on each timeout reset
    pending_cc: jnp.ndarray     # [G] bool
    ltt: jnp.ndarray            # [G] i32 — leader-transfer target (0 none)
    is_ltt: jnp.ndarray         # [G] bool — local node is transfer target

    # peer books [G, P]
    pid: jnp.ndarray            # peer replica ids (0 = empty slot)
    kind: jnp.ndarray           # K_ABSENT/K_VOTER/K_NON_VOTING/K_WITNESS
    match: jnp.ndarray          # i32
    next: jnp.ndarray           # i32
    pstate: jnp.ndarray         # R_RETRY/R_WAIT/R_REPLICATE/R_SNAPSHOT
    active: jnp.ndarray         # bool — recent contact (checkQuorum)
    psnap: jnp.ndarray          # i32 — pending install-snapshot index
    vresp: jnp.ndarray          # bool — vote response received this election
    vgrant: jnp.ndarray         # bool — vote granted

    # log [G, CAP] ring + cursors
    lt: jnp.ndarray             # [G, CAP] i32 — term of entry at index i (slot i & (CAP-1))
    lcc: jnp.ndarray            # [G, CAP] bool — entry is a config change
    snap_index: jnp.ndarray     # [G] i32 — last snapshot index (ring floor)
    snap_term: jnp.ndarray      # [G] i32
    last: jnp.ndarray           # [G] i32
    committed: jnp.ndarray      # [G] i32
    processed: jnp.ndarray      # [G] i32 — released to the apply pipeline
    stable: jnp.ndarray         # [G] i32 — handed to the fsync pipeline

    # ReadIndex circular book [G, RI] (+ acks [G, RI, P])
    ri_low: jnp.ndarray
    ri_high: jnp.ndarray
    ri_index: jnp.ndarray
    ri_acks: jnp.ndarray        # [G, RI, P] bool
    ri_head: jnp.ndarray        # [G] i32
    ri_count: jnp.ndarray       # [G] i32

    # host-escalation flag: shard touched a path the kernel does not model
    # (e.g. a peer needs an InstallSnapshot stream) — host must intervene
    needs_host: jnp.ndarray     # [G] bool

    # device quiesce (quiesce.go state machine folded into the step,
    # quiesce.py its plain reference): an enabled lane idle for
    # e_timeout*10 ticks raises its quiesced mask, tells its peers (the
    # word rides its heartbeat lanes, params.QUIESCE_WORD) and stops
    # taking live ticks (no elections, no heartbeats); an awake lane
    # whose own idle clock is half way follows a peer's word; any
    # non-heartbeat inbox or client activity, or a heartbeat more than
    # e_timeout ticks after the entry, wakes it and bumps quiesce_epoch
    # (the wake counter the quiesce invariants key on)
    quiesce_on: jnp.ndarray     # [G] bool — per-lane enable (Config.quiesce)
    # [G] i32 — ticks since last activity; frozen while quiesced (at the
    # threshold: entered on its own clock; under it: on a peer's word)
    idle_tick: jnp.ndarray
    quiesced: jnp.ndarray       # [G] bool — device-resident quiesced mask
    quiesce_epoch: jnp.ndarray  # [G] i32 — wakes so far (monotone)

    # inline payload slot ring [G, CAP] i32 (SURVEY §7: small fixed-width
    # values on device; bigger payloads stay host-side keyed by index).
    # None unless kp.inline_payloads — the plain path carries no ring.
    lv: jnp.ndarray | None = None


def init_state(
    kp: P.KernelParams,
    num_shards: int,
    replica_id,
    peer_ids,
    peer_kinds=None,
    election_timeout: int = 10,
    heartbeat_timeout: int = 1,
    check_quorum: bool = False,
    pre_vote: bool = False,
    seeds=None,
    quiesce: bool = False,
) -> ShardState:
    """Build a fresh [G] state.

    ``replica_id``: scalar or [G] — the local replica id per shard.
    ``peer_ids``: [P] or [G, P] replica ids (0 marks an empty slot).
    ``peer_kinds``: same shape, defaults to K_VOTER for non-empty slots.
    """
    G, Pn, CAP, RI = num_shards, kp.num_peers, kp.log_cap, kp.readindex_cap
    z = lambda *s: np.zeros((G, *s), np.int32)  # noqa: E731
    zb = lambda *s: np.zeros((G, *s), bool)  # noqa: E731

    rid = np.broadcast_to(np.asarray(replica_id, np.int32), (G,)).copy()
    pids = np.asarray(peer_ids, np.int32)
    if pids.ndim == 1:
        pids = np.broadcast_to(pids, (G, Pn)).copy()
    if peer_kinds is None:
        kinds = np.where(pids != 0, P.K_VOTER, P.K_ABSENT).astype(np.int32)
    else:
        kinds = np.asarray(peer_kinds, np.int32)
        if kinds.ndim == 1:
            kinds = np.broadcast_to(kinds, (G, Pn)).copy()
    if seeds is None:
        seeds = (
            np.arange(1, G + 1, dtype=np.int64) * 2654435761 % (1 << 31)
            + rid.astype(np.int64) * 40503
        ) % (1 << 31)
        seeds = seeds.astype(np.int32)
    et = np.full((G,), election_timeout, np.int32)
    rand0 = np.asarray(
        [
            P.randomized_timeout(int(seeds[g]), 0, int(et[g]))
            for g in range(G)
        ],
        np.int32,
    )

    is_nv = np.zeros((G,), bool)
    is_wt = np.zeros((G,), bool)
    for g in range(G):
        slot = np.nonzero(pids[g] == rid[g])[0]
        if slot.size:
            is_nv[g] = kinds[g, slot[0]] == P.K_NON_VOTING
            is_wt[g] = kinds[g, slot[0]] == P.K_WITNESS
    role = np.where(is_wt, P.WITNESS, np.where(is_nv, P.NON_VOTING, P.FOLLOWER))

    return ShardState(
        replica_id=jnp.asarray(rid),
        seed=jnp.asarray(seeds, jnp.int32),
        e_timeout=jnp.asarray(et),
        h_timeout=jnp.full((G,), heartbeat_timeout, jnp.int32),
        check_quorum=jnp.full((G,), check_quorum, bool),
        pre_vote=jnp.full((G,), pre_vote, bool),
        role=jnp.asarray(role.astype(np.int32)),
        term=jnp.asarray(z()),
        vote=jnp.asarray(z()),
        leader=jnp.asarray(z()),
        applied=jnp.asarray(z()),
        e_tick=jnp.asarray(z()),
        h_tick=jnp.asarray(z()),
        rand_timeout=jnp.asarray(rand0),
        rand_counter=jnp.asarray(z()),
        pending_cc=jnp.asarray(zb()),
        ltt=jnp.asarray(z()),
        is_ltt=jnp.asarray(zb()),
        pid=jnp.asarray(pids),
        kind=jnp.asarray(kinds),
        match=jnp.asarray(z(Pn)),
        next=jnp.asarray(z(Pn) + 1),
        pstate=jnp.asarray(z(Pn)),
        active=jnp.asarray(zb(Pn)),
        psnap=jnp.asarray(z(Pn)),
        vresp=jnp.asarray(zb(Pn)),
        vgrant=jnp.asarray(zb(Pn)),
        lt=jnp.asarray(z(CAP)),
        lcc=jnp.asarray(zb(CAP)),
        lv=jnp.asarray(z(CAP)) if kp.inline_payloads else None,
        snap_index=jnp.asarray(z()),
        snap_term=jnp.asarray(z()),
        last=jnp.asarray(z()),
        committed=jnp.asarray(z()),
        processed=jnp.asarray(z()),
        stable=jnp.asarray(z()),
        ri_low=jnp.asarray(z(RI)),
        ri_high=jnp.asarray(z(RI)),
        ri_index=jnp.asarray(z(RI)),
        ri_acks=jnp.asarray(zb(RI, Pn)),
        ri_head=jnp.asarray(z()),
        ri_count=jnp.asarray(z()),
        needs_host=jnp.asarray(zb()),
        quiesce_on=jnp.full((G,), quiesce, bool),
        idle_tick=jnp.asarray(z()),
        quiesced=jnp.asarray(zb()),
        quiesce_epoch=jnp.asarray(z()),
    )


def inject_rows(state: ShardState, lanes, rows: dict) -> ShardState:
    """``state`` with the rows ``lanes`` set to a batch of admissions:
    ``rows`` holds what differs from lane to lane, the rest is a fresh
    follower's (``KernelEngine._flush_injections`` builds the batch)."""
    s = state

    def put(arr, vals):
        # route sub-32-bit scatters through int32: non-uniform-index
        # scatters on bool operands silently drop writes on TPU past ~3k
        # rows (the _set1 miscompile, core/kernel.py) — an admission
        # batch is exactly that shape
        if arr.dtype == jnp.bool_:
            vals_i = (vals.astype(jnp.int32) if hasattr(vals, "astype")
                      else vals)     # a Python constant sets as it is
            return (arr.astype(jnp.int32).at[lanes].set(vals_i)
                    .astype(bool))
        return arr.at[lanes].set(vals)

    last = rows["last"]
    return s._replace(
        replica_id=put(s.replica_id, rows["replica_id"]),
        seed=put(s.seed, rows["seed"]),
        rand_timeout=put(s.rand_timeout, rows["rand_timeout"]),
        rand_counter=put(s.rand_counter, 0),
        e_timeout=put(s.e_timeout, rows["e_timeout"]),
        h_timeout=put(s.h_timeout, rows["h_timeout"]),
        check_quorum=put(s.check_quorum, rows["check_quorum"]),
        pre_vote=put(s.pre_vote, rows["pre_vote"]),
        role=put(s.role, rows["role"]),
        term=put(s.term, rows["term"]),
        vote=put(s.vote, rows["vote"]),
        leader=put(s.leader, 0),
        applied=put(s.applied, rows["applied"]),
        e_tick=put(s.e_tick, 0),
        h_tick=put(s.h_tick, 0),
        pending_cc=put(s.pending_cc, False),
        ltt=put(s.ltt, 0),
        is_ltt=put(s.is_ltt, False),
        pid=put(s.pid, rows["pid"]),
        kind=put(s.kind, rows["kind"]),
        match=put(s.match, 0),
        next=put(s.next, (last + 1)[:, None]),
        pstate=put(s.pstate, P.R_RETRY),
        active=put(s.active, False),
        psnap=put(s.psnap, 0),
        vresp=put(s.vresp, False),
        vgrant=put(s.vgrant, False),
        lt=put(s.lt, rows["lt"]),
        lcc=put(s.lcc, rows["lcc"]),
        snap_index=put(s.snap_index, rows["snap_index"]),
        snap_term=put(s.snap_term, rows["snap_term"]),
        last=put(s.last, last),
        committed=put(s.committed, rows["committed"]),
        processed=put(s.processed, rows["applied"]),
        stable=put(s.stable, last),
        ri_head=put(s.ri_head, 0),
        ri_count=put(s.ri_count, 0),
        needs_host=put(s.needs_host, False),
        quiesce_on=put(s.quiesce_on, rows["quiesce_on"]),
        idle_tick=put(s.idle_tick, 0),
        quiesced=put(s.quiesced, False),
        quiesce_epoch=put(s.quiesce_epoch, 0),
    )


class Inbox(NamedTuple):
    """Fixed-width inbound message block, [G, K] lanes (+ [G, K, E] entries).

    Message fields mirror raftpb.Message (message.go:6-20) minus snapshots —
    InstallSnapshot and ConfigChangeEvent are host-mediated and never enter
    the kernel."""

    mtype: jnp.ndarray      # i32 (NOOP = empty slot when from == 0)
    from_: jnp.ndarray      # i32 replica id (0 = empty slot)
    term: jnp.ndarray
    log_term: jnp.ndarray
    log_index: jnp.ndarray
    commit: jnp.ndarray
    reject: jnp.ndarray     # bool
    hint: jnp.ndarray
    hint_high: jnp.ndarray
    n_ent: jnp.ndarray      # i32 — entries carried (replicate)
    ent_term: jnp.ndarray   # [G, K, E] i32
    ent_cc: jnp.ndarray     # [G, K, E] bool
    # inline payload lanes; None (default) when the sender keeps payloads
    # host-side (the kernel substitutes zeros)
    ent_val: jnp.ndarray | None = None


def empty_inbox(kp: P.KernelParams, num_shards: int) -> Inbox:
    G, K, E = num_shards, kp.inbox_cap, kp.msg_entries
    z = lambda *s: jnp.zeros((G, *s), jnp.int32)  # noqa: E731
    # ent_val is materialized only under inline_payloads so the
    # self-driving loop's carry matches route()'s output structure
    return Inbox(
        mtype=z(K), from_=z(K), term=z(K), log_term=z(K), log_index=z(K),
        commit=z(K), reject=jnp.zeros((G, K), bool), hint=z(K), hint_high=z(K),
        n_ent=z(K), ent_term=z(K, E), ent_cc=jnp.zeros((G, K, E), bool),
        ent_val=z(K, E) if kp.inline_payloads else None,
    )


class StepInput(NamedTuple):
    """Everything a shard consumes in one step besides its inbox."""

    # proposals [G, B]: valid + is-config-change marker; payloads stay host-side
    prop_valid: jnp.ndarray     # [G, B] bool
    prop_cc: jnp.ndarray        # [G, B] bool
    # batched ReadIndex request (host batches all pending reads into one ctx
    # per shard per step, mirroring node.handleReadIndex's batch ctx)
    ri_valid: jnp.ndarray       # [G] bool
    ri_low: jnp.ndarray         # [G] i32
    ri_high: jnp.ndarray        # [G] i32
    # leadership transfer request (0 = none)
    transfer_to: jnp.ndarray    # [G] i32
    # clock
    tick: jnp.ndarray           # [G] bool — advance the logical clock
    quiesced: jnp.ndarray       # [G] bool — tick in quiesced mode
    # host acks: RSM applied cursor (monotonic)
    applied: jnp.ndarray        # [G] i32
    # inline proposal payloads (device-SM path); None = host-side payloads
    prop_val: jnp.ndarray | None = None


def empty_input(kp: P.KernelParams, num_shards: int) -> StepInput:
    G, B = num_shards, kp.proposal_cap
    z = lambda *s: jnp.zeros((G, *s), jnp.int32)  # noqa: E731
    zb = lambda *s: jnp.zeros((G, *s), bool)  # noqa: E731
    return StepInput(
        prop_valid=zb(B), prop_cc=zb(B),
        ri_valid=zb(), ri_low=z(), ri_high=z(),
        transfer_to=z(), tick=zb(), quiesced=zb(), applied=z(),
    )


class StepOutput(NamedTuple):
    """Per-shard, per-step results (the device-side pb.Update contract —
    update.go:74-112 re-expressed as fixed lanes)."""

    # responses to inbox slots [G, K]
    r_type: jnp.ndarray     # i32 (0 = none; NoOP uses its real enum value)
    r_to: jnp.ndarray
    r_term: jnp.ndarray
    r_log_index: jnp.ndarray
    r_reject: jnp.ndarray   # bool
    r_hint: jnp.ndarray
    r_hint_high: jnp.ndarray

    # replicate/vote lanes per peer [G, P]
    s_rep: jnp.ndarray      # bool — send a Replicate to this peer
    s_prev_index: jnp.ndarray
    s_prev_term: jnp.ndarray
    s_commit: jnp.ndarray
    s_n_ent: jnp.ndarray
    s_ent_term: jnp.ndarray  # [G, P, E]
    s_ent_cc: jnp.ndarray    # [G, P, E] bool
    # [G, P, E] i32 inline payload lanes; None unless kp.inline_payloads
    s_ent_val: jnp.ndarray | None
    s_vote: jnp.ndarray      # i32: 0 none, 1 RequestVote, 2 RequestPreVote
    s_vote_term: jnp.ndarray
    s_vote_lindex: jnp.ndarray
    s_vote_lterm: jnp.ndarray
    s_vote_hint: jnp.ndarray
    s_hb: jnp.ndarray        # bool — heartbeat to this peer
    s_hb_commit: jnp.ndarray
    s_hb_low: jnp.ndarray
    s_hb_high: jnp.ndarray
    s_timeout_now: jnp.ndarray  # bool
    s_need_snapshot: jnp.ndarray  # bool — host must stream a snapshot
    # bool — witness peer fell behind compaction: the host answers with a
    # stripped file-less witness snapshot (raft.go:728) WITHOUT evicting
    s_wit_snap: jnp.ndarray

    # persistence + apply pipeline [G]
    save_first: jnp.ndarray
    save_last: jnp.ndarray   # save (save_first..save_last]... inclusive range when >= first
    apply_first: jnp.ndarray
    apply_last: jnp.ndarray
    term: jnp.ndarray        # pb.State triple for SaveRaftState
    vote: jnp.ndarray
    commit: jnp.ndarray

    # ReadIndex results [G, RI]
    rtr_valid: jnp.ndarray
    rtr_index: jnp.ndarray
    rtr_low: jnp.ndarray
    rtr_high: jnp.ndarray
    # dropped batched-read request (host re-queues / fails it)
    ri_dropped: jnp.ndarray  # [G] bool

    # proposal fates [G, B]
    prop_accepted: jnp.ndarray  # bool
    prop_index: jnp.ndarray     # assigned log index
    prop_term: jnp.ndarray      # assigned term

    # events [G]
    leader: jnp.ndarray
    leader_term: jnp.ndarray
    needs_host: jnp.ndarray


# ---------------------------------------------------------------------------
# The round's two crossings, as one column table.
#
# An engine round sends ONE [G, Wu] int32 array up (the staged Inbox and
# StepInput) and reads ONE [G, Wd] int32 array down (the activity flags,
# the ``active`` column, every StepOutput field, the save window's terms).
# Both layouts are derived here, from CONTRACTS and the geometry, so the
# host builders, the jitted program's unpack/pack (core/round.py,
# parallel/round.py) and the host view of the download cannot drift: a
# field is ``width`` consecutive columns from ``start``, its trailing
# ``shape`` flattened row-major; a bool rides as a 0/1 column.  Bytes are
# not the cost of a crossing, the crossing is (PERF.md section 5): rows are
# not compacted, nothing is bit-packed.
# ---------------------------------------------------------------------------

# Message-class order of the download's leading flag columns (what
# core/kernel.py ``output_row_flags`` produces): the engine keys on them
# to decide which of a row's message fields to read at all.
FLAG_CLASSES = ("resp", "rep", "hb", "vote", "timeout_now",
                "need_snapshot", "wit_snap", "rtr")

# Bits of the download's ``active`` column (core/round.py ``row_activity``
# writes it; the engine retires the rows where it is not 0): the row's
# output holds something to do (a flag, a dropped read, an escalation, a
# save or apply window that is not empty); its (term, vote, commit) moved
# in this step (persisted even when no message goes out); its leader or
# term moved (the leader edge).  A row nothing reaches (no tick, no
# message) reads 0.
ACTIVE_OUTPUT, ACTIVE_TRIPLE, ACTIVE_LEADER = 1, 2, 4

#: symbolic contract axis -> the KernelParams field holding its extent
#: (G is the free variable; the capacity model sizes by the same table)
AXIS_PARAMS = {"P": "num_peers", "CAP": "log_cap", "K": "inbox_cap",
               "E": "msg_entries", "B": "proposal_cap", "RI": "readindex_cap"}


class Column(NamedTuple):
    field: str       # name within its table
    start: int       # first column
    width: int       # columns: prod(shape)
    dtype: str       # "i32" | "bool" (as the field is typed off the wire)
    shape: tuple     # trailing shape after [G]


class RoundColumns(NamedTuple):
    up: tuple            # Inbox fields, then StepInput fields
    up_width: int
    down: tuple          # "flags", "active", StepOutput fields, "save_terms"
    down_width: int
    save_window: int     # S


def save_window(kp) -> int:
    """S: the ring entries per lane whose terms ride the download.  A step
    appends at most ``B + 1`` entries on a leader (a no-op on election,
    then the proposals) and ``K * E`` on a follower (``save_first`` is the
    lowest index written since ``stable``, core/kernel.py), rounded up to
    the power of two the gather-free window read wants."""
    s = getattr(kp, "save_window", 0)
    if not s:
        want = max(kp.proposal_cap + 1, kp.inbox_cap * kp.msg_entries)
        s = 1 << (want - 1).bit_length()
    return min(s, kp.log_cap)


def _class_columns(cls, kp, start: int, without=()) -> tuple[list, int]:
    cols = []
    for f in cls._fields:
        if f in without:
            continue
        axes, rest = CONTRACTS[cls.__name__][f][1:].split("]", 1)
        tags = rest.split()
        if "optional" in tags and not kp.inline_payloads:
            continue
        shape = tuple(int(getattr(kp, AXIS_PARAMS[a.strip()]))
                      for a in axes.split(",")[1:])
        cols.append(Column(f, start, math.prod(shape), tags[0], shape))
        start += cols[-1].width
    return cols, start


@functools.lru_cache(maxsize=None)
def round_columns(kp) -> RoundColumns:
    """The column table of both crossings at ``kp``'s geometry (``kp`` is
    a KernelParams, or anything hashable with its attributes)."""
    box, w = _class_columns(Inbox, kp, 0)
    inp, wu = _class_columns(StepInput, kp, w)
    s = save_window(kp)
    flags = Column("flags", 0, len(FLAG_CLASSES), "bool",
                   (len(FLAG_CLASSES),))
    active = Column("active", flags.width, 1, "i32", ())
    out, w = _class_columns(StepOutput, kp, flags.width + 1)
    terms = Column("save_terms", w, s, "i32", (s,))
    return RoundColumns(up=tuple(box + inp), up_width=wu,
                        down=(flags, active, *out, terms),
                        down_width=w + s, save_window=s)


def pack_columns(cols, values: dict):
    """[G, W] int32 from ``values[field]`` for every column (device side:
    jnp; the host builders write through ``column_views`` instead)."""
    return jnp.concatenate(
        [values[c.field].astype(jnp.int32).reshape(
            values[c.field].shape[0], c.width) for c in cols], axis=1)


def column_views(cols, packed) -> dict:
    """field -> the [G, *shape] slice of a packed [G, W] array, still
    int32 (numpy: a writable view of its columns, what the host builders
    write through)."""
    return {c.field: packed[:, c.start:c.start + c.width].reshape(
        (packed.shape[0],) + c.shape) for c in cols}


def column_value(c: Column, packed):
    """Column ``c`` of a packed [G, W] array as its field: [G, *shape],
    compared ``!= 0`` where the field is a bool (numpy or jnp)."""
    x = column_views((c,), packed)[c.field]
    return x != 0 if c.dtype == "bool" else x


def unpack_columns(cls, cols, packed):
    """Rebuild the NamedTuple ``cls`` from its columns of ``packed``, each
    field with its contract dtype; a field without columns is None."""
    fields = dict.fromkeys(cls._fields)
    fields.update((c.field, column_value(c, packed))
                  for c in cols if c.field in fields)
    return cls(**fields)


def unpack_upload(kp, up) -> tuple[Inbox, StepInput]:
    cols = round_columns(kp).up
    return (unpack_columns(Inbox, cols, up),
            unpack_columns(StepInput, cols, up))


def pack_download(kp, flags, active, out: StepOutput, save_terms):
    return pack_columns(
        round_columns(kp).down,
        {**out._asdict(), "flags": flags, "active": active,
         "save_terms": save_terms})


# ---------------------------------------------------------------------------
# The resident form: what an engine keeps on the device between rounds.
#
# A ShardState is 45 device arrays, and every round let 45 go: jaxlib's
# array destructor gives the interpreter lock up to free each buffer and
# has to take it back, one wait per array for whichever thread holds it
# (PERF.md section 6, PR 28: 528 ms for one state's arrays beside three
# busy threads, on the chip).  So between rounds the state is THREE arrays:
# every field but the rings as columns of one [G, Ws] int32 array, laid
# out by the same table as the crossings, and the rings as they are (a
# concatenate would copy their megabytes every round).  ``pack_state`` /
# ``unpack_state`` are the only two functions that convert, and both are
# traced inside programs, never eager: the round's entry (core/round.py,
# parallel/round.py) and the small programs below.
# ---------------------------------------------------------------------------

#: the ShardState fields that stay arrays of their own in the resident form
RING_FIELDS = ("lt", "lcc", "lv")


class ResidentState(NamedTuple):
    cols: jnp.ndarray           # [G, Ws] i32: every other field, a bool 0/1
    lt: jnp.ndarray             # [G, CAP] i32
    lcc: jnp.ndarray            # [G, CAP] bool
    lv: jnp.ndarray | None = None   # [G, CAP] i32 where kp.inline_payloads


@functools.lru_cache(maxsize=None)
def state_columns(kp) -> tuple[tuple, int]:
    """The columns of ``ResidentState.cols`` at ``kp``'s geometry, and
    their width ``Ws``."""
    cols, w = _class_columns(ShardState, kp, 0, without=RING_FIELDS)
    return tuple(cols), w


def pack_state(kp, s: ShardState) -> ResidentState:
    return ResidentState(
        cols=pack_columns(state_columns(kp)[0], s._asdict()),
        lt=s.lt, lcc=s.lcc, lv=s.lv)


def unpack_state(kp, r: ResidentState) -> ShardState:
    s = unpack_columns(ShardState, state_columns(kp)[0], r.cols)
    return s._replace(lt=r.lt, lcc=r.lcc, lv=r.lv)


@functools.lru_cache(maxsize=None)
def inbox_columns(kp) -> tuple[tuple, int]:
    """The Inbox columns of the upload (they lead it) and their width
    ``Wi``: the layout of the mesh backend's carried inbox, ONE [G, Wi]
    int32 array between rounds."""
    cols = tuple(c for c in round_columns(kp).up if c.field in Inbox._fields)
    return cols, cols[-1].start + cols[-1].width


def box_senders(kp, box):
    """[G, K] sender ids of a carried [G, Wi] inbox (traced inside a
    program: the mesh's ``box_from``, the engines' collection)."""
    return column_value(
        next(c for c in inbox_columns(kp)[0] if c.field == "from_"), box)


@functools.lru_cache(maxsize=None)
def resident_program(kp, fn, static_argnames=()):
    """``fn(state, ...)`` as a jitted program that takes the resident form
    in the state's place (the fleet, health and invariant reductions, a
    lane's health row).  One per ``(kp, fn)`` for the process."""
    return jax.jit(
        lambda resident, *a, **kw: fn(unpack_state(kp, resident), *a, **kw),
        static_argnames=static_argnames)


@functools.lru_cache(maxsize=None)
def pack_program(kp, placement=None):
    """``pack_state`` jitted, its result placed as ``placement`` says (the
    backend's sharding along G, or None on a single device): what the
    ``engine.state`` setter runs."""
    return jax.jit(lambda s: pack_state(kp, s), out_shardings=placement)


@functools.lru_cache(maxsize=None)
def view_program(kp, placement=None):
    """The packed columns as the ShardState fields they hold (the rings
    None: the caller hands the resident ones over as they are): what the
    ``engine.state`` getter runs."""
    return jax.jit(
        lambda cols: unpack_state(kp, ResidentState(cols, None, None)),
        out_shardings=placement)


@functools.lru_cache(maxsize=None)
def box_view_program(kp, placement=None):
    """The mesh backend's carried [G, Wi] inbox as the Inbox it holds
    (``MeshDispatch.box``, for callers outside a round)."""
    return jax.jit(
        lambda box: unpack_columns(Inbox, inbox_columns(kp)[0], box),
        out_shardings=placement)


@functools.lru_cache(maxsize=None)
def inject_program(kp, placement=None):
    """``inject_rows`` on the resident form, jitted, its result placed as
    ``placement`` says.  One program per geometry and placement for the
    whole process: engines of one geometry share its compiles."""
    return jax.jit(
        lambda resident, lanes, rows: pack_state(
            kp, inject_rows(unpack_state(kp, resident), lanes, rows)),
        out_shardings=placement)


@functools.lru_cache(maxsize=None)
def write_cells_program(placement=None):
    """``cols`` with ``cols[cells[0, i], cells[1, i]] = cells[2, i]`` for
    every ``i``: the one program behind a lane's clearing and a
    membership's peer-book write (``cells`` is one [3, N] int32 upload; a
    cell written twice must carry the same value both times)."""
    return jax.jit(
        lambda cols, cells: cols.at[cells[0], cells[1]].set(cells[2]),
        out_shardings=placement)

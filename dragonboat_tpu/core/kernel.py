"""The batched Raft step kernel.

One jitted call advances **every shard one step**: drain the inbox lanes,
serve the batched ReadIndex request, append proposals, apply the transfer
request, tick the logical clock, then materialize one coalesced send phase
(≤1 Replicate + ≤1 Heartbeat per peer per step).  This is the TPU-first
re-expression of the reference's per-goroutine step loop
(``engine.go:1230 stepWorkerMain`` → ``node.go:1161 handleEvents``): the
scheduler becomes a vmap axis, the per-message sends become end-of-step
lanes, and the handler matrix (``raft.go:2332``) becomes masked updates —
under vmap every branch runs for every shard, so the code is written
branchless from the start.

Semantics parity is with :mod:`dragonboat_tpu.core.pycore` (itself cited
against ``/root/reference/internal/raft/raft.go``); the differential suite in
``tests/test_kernel_differential.py`` drives both on identical inputs.

Control-flow divergences from the reference (documented, behavior-safe):

- sends are coalesced per step; the content of a Replicate reflects
  end-of-step flow-control state rather than mid-step snapshots;
- proposals and reads are host-routed to the leader replica, so follower
  redirect paths never execute on device;
- InstallSnapshot / ConfigChangeEvent / LogQuery are host-mediated through
  the pycore slow path (SURVEY §7 "masked slow path");
- entry payloads are not on device: the ring stores terms + config-change
  markers, the host mirrors payloads keyed by (shard, index).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.core import params as P
from dragonboat_tpu.core.kstate import (
    Inbox,
    ShardState,
    StepInput,
    StepOutput,
)

I32 = jnp.int32
INT_MAX = jnp.iinfo(jnp.int32).max
MT = pb.MessageType

# Contracts for the kernel-local structs (grammar: core/kstate.py
# CONTRACTS).  These are PER-SHARD shapes — the kernel body runs under
# vmap, so there is no [G] axis here; scalars are "[]".  part=G: the
# values are still per-group data (each group computes its own), so at
# the mesh level they live G-sharded like the kstate structs.
CONTRACTS = {
    "Effects": {
        "need_rep": "[P] bool part=G",
        "need_hb": "[] bool part=G",
        "hb_low": "[] i32 part=G",
        "hb_high": "[] i32 part=G",
        "send_vote": "[] i32 part=G",
        "vote_hint": "[] i32 part=G",
        "send_tn": "[P] bool part=G",
        "rtr_valid": "[RI] bool part=G",
        "rtr_index": "[RI] i32 part=G",
        "rtr_low": "[RI] i32 part=G",
        "rtr_high": "[RI] i32 part=G",
        "rtr_n": "[] i32 part=G",
        "save_from": "[] i32 part=G",
        "ri_dropped": "[] bool part=G",
    },
    "_Pre": {
        "act": "[] bool part=G",
        "is_leader": "[] bool part=G",
        "is_candidate": "[] bool part=G",
        "is_follower_like": "[] bool part=G",
        "sender_known": "[] bool part=G",
        "sender_slot": "[] i32 part=G",
        "noop_reply": "[] bool part=G",
    },
    "_Resp": {
        "r_type": "[] i32 part=G",
        "r_to": "[] i32 part=G",
        "r_term": "[] i32 part=G",
        "r_log_index": "[] i32 part=G",
        "r_reject": "[] bool part=G",
        "r_hint": "[] i32 part=G",
        "r_hint_high": "[] i32 part=G",
    },
}


def sel(c, a, b):
    return jnp.where(c, a, b)


def mrep(s: ShardState, mask, **kw) -> ShardState:
    """Masked replace: set fields where mask (scalar bool) holds."""
    upd = {}
    for k, v in kw.items():
        old = getattr(s, k)
        upd[k] = jnp.where(mask, v, old)
    return s._replace(**upd)


class Effects(NamedTuple):
    """Step-local accumulator consumed by the send phase."""

    need_rep: jnp.ndarray       # [P] bool
    need_hb: jnp.ndarray        # bool
    hb_low: jnp.ndarray
    hb_high: jnp.ndarray
    send_vote: jnp.ndarray      # 0 none / 1 RequestVote / 2 RequestPreVote
    vote_hint: jnp.ndarray
    send_tn: jnp.ndarray        # [P] bool — TimeoutNow
    rtr_valid: jnp.ndarray      # [RI]
    rtr_index: jnp.ndarray
    rtr_low: jnp.ndarray
    rtr_high: jnp.ndarray
    rtr_n: jnp.ndarray
    save_from: jnp.ndarray      # min appended/truncated index this step
    ri_dropped: jnp.ndarray


def _empty_effects(kp: P.KernelParams) -> Effects:
    Pn, RI = kp.num_peers, kp.readindex_cap
    z = lambda *s: jnp.zeros(s, I32)  # noqa: E731
    zb = lambda *s: jnp.zeros(s, bool)  # noqa: E731
    return Effects(
        need_rep=zb(Pn), need_hb=zb(), hb_low=z(), hb_high=z(),
        send_vote=z(), vote_hint=z(), send_tn=zb(Pn),
        rtr_valid=zb(RI), rtr_index=z(RI), rtr_low=z(RI), rtr_high=z(RI),
        rtr_n=z(), save_from=jnp.asarray(INT_MAX, I32), ri_dropped=zb(),
    )


# ---------------------------------------------------------------------------
# log-ring helpers (two-tier view collapsed to ring + snapshot floor;
# parity logentry.go:97-156 term resolution)
# ---------------------------------------------------------------------------


def _slot(kp: P.KernelParams, idx):
    return idx & (kp.log_cap - 1)


def log_term_at(kp: P.KernelParams, s: ShardState, idx):
    """(term, compacted, unavailable) for index idx."""
    in_ring = (idx > s.snap_index) & (idx <= s.last)
    t = sel(
        idx == 0,
        0,
        sel(idx == s.snap_index, s.snap_term,
            sel(in_ring, _get1(kp, s.lt, _slot(kp, idx)), 0)),
    )
    compacted = idx < s.snap_index
    unavailable = idx > s.last
    return t, compacted, unavailable


def match_term(kp, s, idx, term):
    t, comp, unav = log_term_at(kp, s, idx)
    return (~comp) & (~unav) & (t == term)


def up_to_date(kp, s, idx, term):
    lt_last, _, _ = log_term_at(kp, s, s.last)
    return (term > lt_last) | ((term == lt_last) & (idx >= s.last))


def _cc_count_in(kp: P.KernelParams, s: ShardState, lo, hi):
    """Count config-change entries with index in (lo, hi] — used to restore
    the pending flag on promotion (raft.go:1075)."""
    j = jnp.arange(kp.log_cap, dtype=I32)
    idx = s.last - ((s.last - j) & (kp.log_cap - 1))
    live = (idx > lo) & (idx <= hi) & (idx > s.snap_index)
    return jnp.sum(sel(live & s.lcc, 1, 0).astype(I32))


# ---------------------------------------------------------------------------
# peer-book helpers (parity remote.go)
# ---------------------------------------------------------------------------


def _self_slot_mask(s: ShardState):
    return (s.pid == s.replica_id) & (s.kind != P.K_ABSENT)


def _voting_mask(s: ShardState):
    return (s.kind == P.K_VOTER) | (s.kind == P.K_WITNESS)


def _num_voting(s: ShardState):
    return jnp.sum(_voting_mask(s).astype(I32))


def _quorum(s: ShardState):
    return _num_voting(s) // 2 + 1


def _is_single_node(s: ShardState):
    return _quorum(s) == 1


def _self_removed(s: ShardState):
    return ~jnp.any(_self_slot_mask(s))


def _sorted_match_quorum_index(kp: P.KernelParams, s: ShardState):
    """The q-th largest match among voting members — the batched
    tryCommit's jnp.sort (mirrors raft.go:911-941 sortMatchValues)."""
    mv = sel(_voting_mask(s), s.match, INT_MAX)
    srt = jnp.sort(mv)  # ascending; absent lanes sort to the end
    nv = _num_voting(s)
    pos = jnp.clip(nv - _quorum(s), 0, s.match.shape[0] - 1)
    return _get1(kp, srt, pos)


def _try_commit(kp, s: ShardState) -> ShardState:
    q = _sorted_match_quorum_index(kp, s)
    t, comp, _ = log_term_at(kp, s, q)
    t = sel(comp, 0, t)
    ok = (q > s.committed) & (t == s.term) & (s.role == P.LEADER)
    return mrep(s, ok, committed=q)


# ---------------------------------------------------------------------------
# state transitions (parity raft.go:960-1130)
# ---------------------------------------------------------------------------


def _next_rand_timeout(s: ShardState):
    counter = s.rand_counter + 1
    mixed = P.splitmix32(
        (s.seed.astype(jnp.uint32) ^ (counter.astype(jnp.uint32) * jnp.uint32(0x632BE5AB)))
    )
    r = (mixed % s.e_timeout.astype(jnp.uint32)).astype(I32)
    return counter, s.e_timeout + r


def _reset(s: ShardState, mask, term, reset_timeout) -> ShardState:
    """Shared reset on every role transition (raft.go:1052 reset)."""
    term_changed = s.term != term
    counter, rand_t = _next_rand_timeout(s)
    self_mask = _self_slot_mask(s)
    s = mrep(
        s, mask,
        term=term,
        vote=sel(term_changed, 0, s.vote),
        e_tick=sel(reset_timeout, 0, s.e_tick),
        rand_counter=sel(reset_timeout, counter, s.rand_counter),
        rand_timeout=sel(reset_timeout, rand_t, s.rand_timeout),
        h_tick=0,
        pending_cc=False,
        ltt=0,
        vresp=jnp.zeros_like(s.vresp),
        vgrant=jnp.zeros_like(s.vgrant),
        match=sel(self_mask, s.last, 0),
        next=jnp.full_like(s.next, 1) * (s.last + 1),
        pstate=jnp.zeros_like(s.pstate),
        active=jnp.zeros_like(s.active),
        psnap=jnp.zeros_like(s.psnap),
        ri_head=0,
        ri_count=0,
        ri_acks=jnp.zeros_like(s.ri_acks),
    )
    return s


def _become_follower(s, mask, term, leader, reset_timeout=True):
    # witnesses/non-votings keep their role on term bumps (raft.go:972-990)
    new_role = sel(
        s.role == P.NON_VOTING, P.NON_VOTING,
        sel(s.role == P.WITNESS, P.WITNESS, P.FOLLOWER),
    )
    s = _reset(s, mask, sel(mask, term, s.term), reset_timeout & mask)
    return mrep(s, mask, role=new_role, leader=leader)


def _set1(arr, idx, val, mask):
    """TPU-safe masked write of one dynamic slot: arr[idx] = val where mask.

    vmapped scalar-index ``.at[i].set`` lowers to a batched scatter, and on
    TPU (jax 0.9.0, v5e) that scatter SILENTLY DROPS writes for sub-32-bit
    element types (bool/int8/int16) once the batch axis exceeds ~3k rows
    with non-uniform indices.  A one-hot select avoids scatter entirely —
    and vectorizes better on the VPU anyway, so it is also the faster
    lowering for the small [P]/[RI]/ring axes this kernel uses."""
    n = arr.shape[0]
    oh = (jnp.arange(n, dtype=I32) == idx) & mask
    return jnp.where(oh, val, arr)


def _set_row(arr, idx, val, mask):
    """Row variant of _set1: arr[idx, :] = val where mask (arr [N, P])."""
    n = arr.shape[0]
    oh = (jnp.arange(n, dtype=I32) == idx) & mask
    return jnp.where(oh[:, None], val, arr)


def onehot_select(oh, arr, axis: int):
    """Reduce ``arr`` along ``axis`` through the one-hot mask ``oh``
    (broadcastable to arr): the shared lowering behind _get1/_get_row and
    the router's lane/source selects.  Exact when at most one mask slot
    is hot (ints sum a single term; bools use any)."""
    if arr.dtype == jnp.bool_:
        return jnp.any(oh & arr, axis=axis)
    return jnp.where(oh, arr, 0).sum(axis=axis).astype(arr.dtype)


def _get1(kp: P.KernelParams, arr, idx):
    """Platform-tuned read of one dynamic slot: arr[idx], idx in [0, N).

    The read-side twin of _set1.  With ``kp.onehot_reads`` (device
    configs) this is a one-hot compare+select+reduce: vmapped dynamic
    indexing lowers to a gather, and on TPU a batched gather serializes
    over the [G] batch axis — the r4 device ladder measured the
    resulting step cost at ~0.32 ms *per group* (256 groups: 130
    ms/step; 1024: 377 ms) against a ~10 µs roofline.  Without the flag
    (CPU configs) it stays plain dynamic indexing — the gather is an
    O(1) load there and the one-hot form measurably loses (37% step time
    across all sites, 3.5x with the rings included).  ``idx`` may be any
    integer shape (the result has idx's shape); every caller passes an
    in-range index (argmax results or ring-masked offsets), so the two
    lowerings are bitwise-identical."""
    if not kp.onehot_reads:
        return arr[idx]
    n = arr.shape[0]
    oh = jnp.expand_dims(idx, -1) == jnp.arange(n, dtype=I32)
    return onehot_select(oh, arr, -1)


def _get_row(kp: P.KernelParams, arr, idx):
    """Row variant of _get1: arr[idx, :] for arr [N, P], scalar idx."""
    if not kp.onehot_reads:
        return arr[idx]
    n = arr.shape[0]
    oh = jnp.arange(n, dtype=I32) == idx
    return onehot_select(oh[:, None], arr, 0)


def _append_one(kp, s: ShardState, mask, term, is_cc,
                val=None) -> ShardState:
    idx = s.last + 1
    slot = _slot(kp, idx)
    lt = _set1(s.lt, slot, term, mask)
    lcc = _set1(s.lcc, slot, is_cc, mask)
    s = s._replace(lt=lt, lcc=lcc)
    if kp.inline_payloads:
        v = jnp.asarray(0, I32) if val is None else val
        s = s._replace(lv=_set1(s.lv, slot, v, mask))
    return mrep(s, mask, last=idx)


def _become_leader(kp, s: ShardState, mask, eff: Effects):
    """Candidate→leader: reset, restore pending-CC flag, append noop
    (p72 raft thesis), broadcast (raft.go:1038)."""
    s2 = _reset(s, mask, s.term, True)
    s2 = mrep(s2, mask, role=P.LEADER, leader=s.replica_id)
    cc_pending = _cc_count_in(kp, s2, s2.committed, s2.last) > 0
    s2 = mrep(s2, mask, pending_cc=cc_pending)
    s2 = _append_one(kp, s2, mask, s2.term, False)
    self_mask = _self_slot_mask(s2)
    s2 = s2._replace(
        match=sel(mask & self_mask, s2.last, s2.match),
        next=sel(mask & self_mask, s2.last + 1, s2.next),
    )
    s2 = _try_commit(kp, s2)
    eff = eff._replace(
        need_rep=sel(mask, jnp.ones_like(eff.need_rep), eff.need_rep),
        save_from=sel(mask, jnp.minimum(eff.save_from, s2.last), eff.save_from),
    )
    return s2, eff


def _campaign(kp, s: ShardState, eff: Effects, mask, allow_prevote=True):
    """Election entry — handleNodeElection (raft.go:1632): pre-vote campaign
    unless transferring; single-node fast paths to leader."""
    # config-change gate (raft.go:1632 handleNodeElection): refuse to
    # campaign only when a CONFIG CHANGE sits committed-but-unapplied —
    # voting safety is log-based, so plain unapplied entries don't
    # matter.  Gating on committed > applied alone is a liveness trap:
    # apply backpressure keeps the window permanently non-empty on a
    # busy host, making elections (and TimeoutNow transfers) impossible
    # exactly when load needs to move
    gate = (s.committed > s.applied) & (
        _cc_count_in(kp, s, s.applied, s.committed) > 0)
    mask = mask & ~gate & ~_self_removed(s)
    use_prevote = s.pre_vote & ~s.is_ltt & allow_prevote
    single = _is_single_node(s)

    # -- pre-vote branch: no term bump (raft.go:1149 preVoteCampaign)
    pv = mask & use_prevote
    s = _reset(s, pv, s.term, True)
    s = mrep(s, pv, role=P.PRE_VOTE_CANDIDATE, leader=0)
    self_mask = _self_slot_mask(s)
    s = s._replace(
        vresp=sel(pv & self_mask, True, s.vresp),
        vgrant=sel(pv & self_mask, True, s.vgrant),
    )
    eff = eff._replace(send_vote=sel(pv & ~single, 2, eff.send_vote))

    # -- real campaign branch (raft.go:1176 campaign)
    rc = mask & (~use_prevote | single)
    hint = sel(s.is_ltt, s.replica_id, 0)
    s = _reset(s, rc, s.term + 1, True)
    s = mrep(s, rc, role=P.CANDIDATE, leader=0, vote=s.replica_id,
             is_ltt=False)
    self_mask = _self_slot_mask(s)
    s = s._replace(
        vresp=sel(rc & self_mask, True, s.vresp),
        vgrant=sel(rc & self_mask, True, s.vgrant),
    )
    eff = eff._replace(
        send_vote=sel(rc & ~single, 1, eff.send_vote),
        vote_hint=sel(rc & ~single, hint, eff.vote_hint),
    )
    s2, eff = _become_leader(kp, s, rc & single, eff)
    return s2, eff


# ---------------------------------------------------------------------------
# readindex book (parity readindex.go)
# ---------------------------------------------------------------------------


def _ri_push(kp, s: ShardState, mask, low, high, index):
    RI = kp.readindex_cap
    full = s.ri_count >= RI
    pos = (s.ri_head + s.ri_count) & (RI - 1)
    do = mask & ~full
    s = s._replace(
        ri_low=_set1(s.ri_low, pos, low, do),
        ri_high=_set1(s.ri_high, pos, high, do),
        ri_index=_set1(s.ri_index, pos, index, do),
        ri_acks=_set_row(s.ri_acks, pos, jnp.zeros_like(s.ri_acks[0]), do),
    )
    s = mrep(s, do, ri_count=s.ri_count + 1)
    # a full book drops the request (host will retry) — bounded-memory analog
    # of the reference's unbounded pending map
    return s, mask & full


def _ri_confirm(kp, s: ShardState, eff: Effects, mask, low, high, sender_slot):
    """Ack ctx from sender; pop every ctx at-or-before once quorum reached
    (readindex.go:73 confirm)."""
    RI = kp.readindex_cap
    arange = jnp.arange(RI, dtype=I32)
    # queue position of each physical slot (0..count-1), INT_MAX if dead
    qpos = (arange - s.ri_head) & (RI - 1)
    live = qpos < s.ri_count
    hit = live & (s.ri_low == low) & (s.ri_high == high)
    hit_any = mask & jnp.any(hit)
    hit_slot = jnp.argmax(hit)
    P_ = s.ri_acks.shape[1]
    oh2 = ((jnp.arange(RI, dtype=I32) == hit_slot)[:, None]
           & (jnp.arange(P_, dtype=I32) == sender_slot)[None, :] & hit_any)
    s = s._replace(ri_acks=jnp.where(oh2, True, s.ri_acks))
    n_acks = jnp.sum(_get_row(kp, s.ri_acks, hit_slot).astype(I32))
    quorum_ok = hit_any & (n_acks + 1 >= _quorum(s))
    pop_n = sel(quorum_ok, _get1(kp, qpos, hit_slot) + 1, 0)
    # pop: emit rtr for queue positions < pop_n
    popping = live & (qpos < pop_n)
    base = eff.rtr_n
    out_pos = base + qpos  # each popped ctx goes to rtr lane base+qpos
    # scatter via explicit loop over RI lanes (RI is small)
    rv, ri_, rl, rh = eff.rtr_valid, eff.rtr_index, eff.rtr_low, eff.rtr_high
    for j in range(RI):
        src = popping & (out_pos == j)
        any_src = jnp.any(src)
        k = jnp.argmax(src)
        rv = rv.at[j].set(sel(any_src, True, rv[j]))
        ri_ = ri_.at[j].set(sel(any_src, _get1(kp, s.ri_index, k), ri_[j]))
        rl = rl.at[j].set(sel(any_src, _get1(kp, s.ri_low, k), rl[j]))
        rh = rh.at[j].set(sel(any_src, _get1(kp, s.ri_high, k), rh[j]))
    eff = eff._replace(
        rtr_valid=rv, rtr_index=ri_, rtr_low=rl, rtr_high=rh,
        rtr_n=base + pop_n,
    )
    s = mrep(s, pop_n > 0,
             ri_head=(s.ri_head + pop_n) & (RI - 1),
             ri_count=s.ri_count - pop_n)
    return s, eff


# ---------------------------------------------------------------------------
# the per-message processor (scan body over K inbox slots)
# ---------------------------------------------------------------------------


class _Pre(NamedTuple):
    """Shared term/role preamble results for one inbound message."""

    act: jnp.ndarray
    is_leader: jnp.ndarray
    is_candidate: jnp.ndarray
    is_follower_like: jnp.ndarray
    sender_known: jnp.ndarray
    sender_slot: jnp.ndarray
    noop_reply: jnp.ndarray


class _Resp(NamedTuple):
    r_type: jnp.ndarray
    r_to: jnp.ndarray
    r_term: jnp.ndarray
    r_log_index: jnp.ndarray
    r_reject: jnp.ndarray
    r_hint: jnp.ndarray
    r_hint_high: jnp.ndarray


def _preamble(kp: P.KernelParams, s: ShardState, m):
    """Term preamble + role folding shared by every handler family —
    raft.go:1540 onMessageTermNotMatched + the candidate fold
    (raft.go:2218).  Returns the updated state and the masks handlers
    key on."""
    valid = m.from_ != 0
    mtype = m.mtype

    slot_hit = (s.pid == m.from_) & (s.kind != P.K_ABSENT)
    sender_known = jnp.any(slot_hit)
    sender_slot = jnp.argmax(slot_hit)

    is_rv_msg = (mtype == MT.REQUEST_VOTE) | (mtype == MT.REQUEST_PREVOTE)
    is_leader_msg = (
        (mtype == MT.REPLICATE)
        | (mtype == MT.HEARTBEAT)
        | (mtype == MT.TIMEOUT_NOW)
        | (mtype == MT.READ_INDEX_RESP)
    )

    drop_rv = (
        valid & is_rv_msg & s.check_quorum & (m.term > s.term)
        & (m.hint != m.from_)
        & (s.leader != 0) & (s.e_tick < s.e_timeout)
    )
    higher = valid & (m.term > s.term) & ~drop_rv
    prevote_expected = (mtype == MT.REQUEST_PREVOTE) | (
        (mtype == MT.REQUEST_PREVOTE_RESP) & ~m.reject
    )
    bump = higher & ~prevote_expected
    new_leader = sel(is_leader_msg, m.from_, 0)
    keep_tick = mtype == MT.REQUEST_VOTE
    s = _become_follower(s, bump, m.term, new_leader, reset_timeout=~keep_tick)

    lower = valid & (m.term < s.term) & (m.term != 0)
    # free-stuck-candidate NoOP (raft.go:1582-1589)
    noop_reply = lower & (
        (mtype == MT.REQUEST_PREVOTE)
        | (is_leader_msg & (s.check_quorum | s.pre_vote))
    )
    ignore = drop_rv | lower

    act = valid & ~ignore
    is_candidate = (s.role == P.CANDIDATE) | (s.role == P.PRE_VOTE_CANDIDATE)
    is_follower_like = (
        (s.role == P.FOLLOWER) | (s.role == P.NON_VOTING) | (s.role == P.WITNESS)
    )

    # candidate + same-term leader message -> become follower (raft.go:2218)
    cand_fold = act & is_candidate & (
        (mtype == MT.REPLICATE) | (mtype == MT.HEARTBEAT)
    )
    s = _become_follower(s, cand_fold, s.term, m.from_)
    is_follower_like = is_follower_like | cand_fold

    pre = _Pre(
        act=act,
        is_leader=s.role == P.LEADER,
        is_candidate=is_candidate,
        is_follower_like=is_follower_like,
        sender_known=sender_known,
        sender_slot=sender_slot,
        noop_reply=noop_reply,
    )
    return s, pre


def _empty_resp(s: ShardState, m, pre: _Pre) -> _Resp:
    return _Resp(
        r_type=sel(pre.noop_reply, MT.NOOP, jnp.asarray(0, I32)),
        r_to=m.from_,
        r_term=s.term,
        r_log_index=jnp.asarray(0, I32),
        r_reject=jnp.asarray(False),
        r_hint=jnp.asarray(0, I32),
        r_hint_high=jnp.asarray(0, I32),
    )


def _h_replicate(kp, s: ShardState, eff: Effects, m, pre: _Pre, r: _Resp):
    """Follower-side Replicate (raft.go:1444 handleReplicateMessage)."""
    E = kp.msg_entries
    h_rep = pre.act & pre.is_follower_like & (m.mtype == MT.REPLICATE)
    s = mrep(s, h_rep, leader=m.from_, e_tick=0)
    below_commit = m.log_index < s.committed
    prev_ok = match_term(kp, s, m.log_index, m.log_term)
    # ring-capacity guard: never let the append run past the term ring —
    # reject instead (the leader backs off; the host drives compaction /
    # snapshot install through the slow path). Keeps the invariant
    # last - snap_index <= log_cap so ring slots never alias.
    over_cap = (m.log_index + m.n_ent - s.snap_index) > kp.log_cap
    accept = h_rep & ~below_commit & prev_ok & ~over_cap
    s = mrep(s, h_rep & over_cap, needs_host=True)
    # conflict scan over the E entry lanes
    ent_idx = m.log_index + 1 + jnp.arange(E, dtype=I32)
    ent_live = jnp.arange(E, dtype=I32) < m.n_ent
    ent_match = jax.vmap(lambda i, t: match_term(kp, s, i, t))(ent_idx, m.ent_term)
    conflict_lane = ent_live & ~ent_match
    any_conflict = jnp.any(conflict_lane)
    first_conflict = jnp.argmax(conflict_lane)  # lane index
    # append entries from the first conflicting lane on
    do_append = accept & any_conflict
    append_from_lane = first_conflict
    # ring writes for lanes >= first_conflict (and live) — scatter-free:
    # each ring slot gathers its (consecutive mod cap) message lane instead
    # of the lanes scattering into the ring (see _set1 on why TPU scatters
    # are off-limits here; the gather form also fuses better)
    write_lane = ent_live & (jnp.arange(E, dtype=I32) >= append_from_lane)
    wmask = do_append & write_lane
    cap = s.lt.shape[0]
    rel = (jnp.arange(cap, dtype=I32) - _slot(kp, m.log_index + 1)) & (cap - 1)
    lane_of_slot = jnp.minimum(rel, E - 1)
    # [CAP]-shaped reads of the [E] message lanes go through _get1 (the
    # dynamic-index form is a G*CAP-row batched gather on device)
    slot_written = (rel < E) & _get1(kp, wmask, lane_of_slot)
    s = s._replace(
        lt=jnp.where(slot_written, _get1(kp, m.ent_term, lane_of_slot), s.lt),
        lcc=jnp.where(slot_written, _get1(kp, m.ent_cc, lane_of_slot), s.lcc),
    )
    if kp.inline_payloads:
        # trace-time contract: a payload-carrying kernel must be fed
        # payload lanes — substituting zeros would silently corrupt
        # follower state machines after a failover
        if m.ent_val is None:
            raise ValueError(
                "inline_payloads kernel requires Inbox.ent_val lanes")
        s = s._replace(
            lv=jnp.where(slot_written, _get1(kp, m.ent_val, lane_of_slot),
                         s.lv))
    new_last_if_append = m.log_index + m.n_ent
    s = mrep(s, do_append, last=new_last_if_append,
             stable=jnp.minimum(s.stable, m.log_index + append_from_lane))
    eff = eff._replace(
        save_from=sel(
            do_append,
            jnp.minimum(eff.save_from, m.log_index + append_from_lane + 1),
            eff.save_from,
        )
    )
    last_idx_msg = m.log_index + m.n_ent
    commit_to = jnp.minimum(
        jnp.minimum(last_idx_msg, m.commit), s.last
    )
    s = mrep(s, accept, committed=jnp.maximum(s.committed, commit_to))
    r = r._replace(
        r_type=sel(h_rep & below_commit, MT.REPLICATE_RESP, r.r_type),
        r_log_index=sel(h_rep & below_commit, s.committed, r.r_log_index),
    )
    r = r._replace(
        r_type=sel(accept, MT.REPLICATE_RESP, r.r_type),
        r_log_index=sel(accept, last_idx_msg, r.r_log_index),
    )
    rejected = h_rep & ~below_commit & (~prev_ok | over_cap)
    r = r._replace(
        r_type=sel(rejected, MT.REPLICATE_RESP, r.r_type),
        r_reject=sel(rejected, True, r.r_reject),
        r_log_index=sel(rejected, m.log_index, r.r_log_index),
        r_hint=sel(rejected, s.last, r.r_hint),
    )
    return s, eff, r


def _h_heartbeat(kp, s: ShardState, eff: Effects, m, pre: _Pre, r: _Resp):
    """Follower-side Heartbeat (raft.go:1398 handleHeartbeatMessage)."""
    h_hb = pre.act & pre.is_follower_like & (m.mtype == MT.HEARTBEAT)
    s = mrep(s, h_hb, leader=m.from_, e_tick=0,
             committed=jnp.maximum(s.committed, jnp.minimum(m.commit, s.last)))
    r = r._replace(
        r_type=sel(h_hb, MT.HEARTBEAT_RESP, r.r_type),
        r_hint=sel(h_hb, m.hint, r.r_hint),
        r_hint_high=sel(h_hb, m.hint_high, r.r_hint_high),
    )
    return s, eff, r


def _h_votereq(kp, s: ShardState, eff: Effects, m, pre: _Pre, r: _Resp):
    """RequestVote / RequestPreVote / TimeoutNow (raft.go:1697,1670,2188)."""
    act = pre.act
    # ---- RequestVote ----
    h_rv = act & (m.mtype == MT.REQUEST_VOTE)
    can_grant = (s.vote == 0) | (s.vote == m.from_)
    utd = up_to_date(kp, s, m.log_index, m.log_term)
    grant = h_rv & can_grant & utd
    s = mrep(s, grant, vote=m.from_, e_tick=0)
    r = r._replace(
        r_type=sel(h_rv, MT.REQUEST_VOTE_RESP, r.r_type),
        r_reject=sel(h_rv & ~grant, True, r.r_reject),
    )
    # ---- RequestPreVote ----
    h_pv = act & (m.mtype == MT.REQUEST_PREVOTE)
    pv_grant = h_pv & (m.term > s.term) & utd
    r = r._replace(
        r_type=sel(h_pv, MT.REQUEST_PREVOTE_RESP, r.r_type),
        r_term=sel(pv_grant, m.term, r.r_term),
        r_reject=sel(h_pv & ~pv_grant, True, r.r_reject),
    )
    # ---- TimeoutNow (follower; raft.go:2188) ----
    h_tn = act & (s.role == P.FOLLOWER) & (m.mtype == MT.TIMEOUT_NOW)
    s = mrep(s, h_tn, is_ltt=True)
    s, eff = _campaign(kp, s, eff, h_tn)
    s = mrep(s, h_tn, is_ltt=False)
    return s, eff, r


def _h_resp(kp, s: ShardState, eff: Effects, m, pre: _Pre, r: _Resp):
    """Response-side handlers: vote tallies, replication flow control,
    heartbeat acks, unreachable, snapshot status (raft.go:2246-2267,
    1878, 1912, 1997, 1975)."""
    act = pre.act
    is_leader = pre.is_leader
    sender_known, sender_slot = pre.sender_known, pre.sender_slot

    # ---- RequestVoteResp (candidate; raft.go:2246) ----
    h_vr = act & (s.role == P.CANDIDATE) & (m.mtype == MT.REQUEST_VOTE_RESP)
    h_vr = h_vr & sender_known & (_get1(kp, s.kind, sender_slot) != P.K_NON_VOTING)
    not_seen = ~_get1(kp, s.vresp, sender_slot)
    s = s._replace(
        vresp=_set1(s.vresp, sender_slot, True, h_vr),
        vgrant=_set1(s.vgrant, sender_slot, ~m.reject, h_vr & not_seen),
    )
    votes_for = jnp.sum(s.vgrant.astype(I32))
    votes_against = jnp.sum((s.vresp & ~s.vgrant).astype(I32))
    q = _quorum(s)
    s, eff = _become_leader(kp, s, h_vr & (votes_for == q), eff)
    s = _become_follower(s, h_vr & (votes_against == q), s.term, 0)

    # ---- RequestPreVoteResp (raft.go:2267) ----
    h_pvr = act & (s.role == P.PRE_VOTE_CANDIDATE) & (
        m.mtype == MT.REQUEST_PREVOTE_RESP
    )
    h_pvr = h_pvr & sender_known & (_get1(kp, s.kind, sender_slot) != P.K_NON_VOTING)
    not_seen = ~_get1(kp, s.vresp, sender_slot)
    s = s._replace(
        vresp=_set1(s.vresp, sender_slot, True, h_pvr),
        vgrant=_set1(s.vgrant, sender_slot, ~m.reject, h_pvr & not_seen),
    )
    votes_for = jnp.sum(s.vgrant.astype(I32))
    votes_against = jnp.sum((s.vresp & ~s.vgrant).astype(I32))
    s, eff = _campaign(kp, s, eff, h_pvr & (votes_for == q),
                       allow_prevote=False)
    s = _become_follower(s, h_pvr & (votes_against == q), s.term, 0)

    # ---- ReplicateResp (leader; raft.go:1878) ----
    h_rr = act & is_leader & (m.mtype == MT.REPLICATE_RESP) & sender_known
    s = s._replace(active=_set1(s.active, sender_slot, True, h_rr))
    old_match = _get1(kp, s.match, sender_slot)
    old_next = _get1(kp, s.next, sender_slot)
    old_pstate = _get1(kp, s.pstate, sender_slot)
    paused = (old_pstate == P.R_WAIT) | (old_pstate == P.R_SNAPSHOT)
    # non-reject: tryUpdate
    ok_resp = h_rr & ~m.reject
    updated = ok_resp & (old_match < m.log_index)
    s = s._replace(
        next=_set1(s.next, sender_slot,
                   jnp.maximum(old_next, m.log_index + 1), ok_resp),
        match=_set1(s.match, sender_slot, m.log_index, updated),
    )
    # wait_to_retry then respondedTo: retry->replicate; snapshot->retry if caught up
    ps = _get1(kp, s.pstate, sender_slot)
    ps = sel(updated & (ps == P.R_WAIT), P.R_RETRY, ps)
    ps = sel(updated & (ps == P.R_RETRY), P.R_REPLICATE, ps)
    snap_caught = _get1(kp, s.match, sender_slot) >= _get1(kp, s.psnap, sender_slot)
    ps = sel(updated & (ps == P.R_SNAPSHOT) & snap_caught, P.R_RETRY, ps)
    s = s._replace(
        pstate=_set1(s.pstate, sender_slot, ps, h_rr),
        psnap=_set1(s.psnap, sender_slot, 0,
                    updated & (old_pstate == P.R_SNAPSHOT) & snap_caught),
    )
    committed_before = s.committed
    s = jax.tree_util.tree_map(
        lambda a, b: sel(updated, a, b), _try_commit(kp, s), s
    )
    commit_advanced = s.committed > committed_before
    # broadcast on commit advance; else resend to the (formerly paused) peer
    eff = eff._replace(
        need_rep=sel(
            updated & commit_advanced, jnp.ones_like(eff.need_rep),
            _set1(eff.need_rep, sender_slot, True,
                  updated & ~commit_advanced & paused),
        )
    )
    # leadership transfer: target caught up -> TimeoutNow (raft.go:1893)
    tn = updated & (s.ltt == m.from_) & (_get1(kp, s.match, sender_slot) == s.last)
    eff = eff._replace(send_tn=_set1(eff.send_tn, sender_slot, True, tn))
    # reject: decreaseTo (remote.go:decreaseTo) + resend
    rej = h_rr & m.reject
    in_replicate = old_pstate == P.R_REPLICATE
    dec_ok_rep = rej & in_replicate & (m.log_index > old_match)
    dec_ok_probe = rej & ~in_replicate & (old_next - 1 == m.log_index)
    new_next = sel(
        in_replicate, old_match + 1,
        jnp.maximum(1, jnp.minimum(m.log_index, m.hint + 1)),
    )
    dec = dec_ok_rep | dec_ok_probe
    dec_ps = sel(dec_ok_rep, P.R_RETRY,
                 sel(dec_ok_probe & (_get1(kp, s.pstate, sender_slot) == P.R_WAIT),
                     P.R_RETRY, _get1(kp, s.pstate, sender_slot)))
    s = s._replace(
        next=_set1(s.next, sender_slot, new_next, dec),
        pstate=_set1(s.pstate, sender_slot, dec_ps, h_rr),
    )
    eff = eff._replace(need_rep=_set1(eff.need_rep, sender_slot, True, dec))

    # ---- HeartbeatResp (leader; raft.go:1912) ----
    h_hr = act & is_leader & (m.mtype == MT.HEARTBEAT_RESP) & sender_known
    s = s._replace(
        active=_set1(s.active, sender_slot, True, h_hr),
        pstate=_set1(s.pstate, sender_slot, P.R_RETRY,
                     h_hr & (_get1(kp, s.pstate, sender_slot) == P.R_WAIT)),
    )
    lagging = _get1(kp, s.match, sender_slot) < s.last
    eff = eff._replace(need_rep=_set1(eff.need_rep, sender_slot, True,
                                      h_hr & lagging))
    conf = h_hr & (m.hint != 0)
    s_c, eff_c = _ri_confirm(kp, s, eff, conf, m.hint, m.hint_high, sender_slot)
    s = jax.tree_util.tree_map(lambda a, b: sel(conf, a, b), s_c, s)
    eff = jax.tree_util.tree_map(lambda a, b: sel(conf, a, b), eff_c, eff)

    # ---- Unreachable (leader; raft.go:1997) ----
    h_un = act & is_leader & (m.mtype == MT.UNREACHABLE) & sender_known
    s = s._replace(pstate=_set1(
        s.pstate, sender_slot, P.R_RETRY,
        h_un & (_get1(kp, s.pstate, sender_slot) == P.R_REPLICATE)))

    # ---- SnapshotStatus (leader, immediate variant; raft.go:1975) ----
    h_ss = act & is_leader & (m.mtype == MT.SNAPSHOT_STATUS) & sender_known
    in_snap = _get1(kp, s.pstate, sender_slot) == P.R_SNAPSHOT
    # becomeWait: next = max(match+1, psnap+1) on success; clear psnap on reject
    nn = sel(
        m.reject, _get1(kp, s.match, sender_slot) + 1,
        jnp.maximum(_get1(kp, s.match, sender_slot) + 1, _get1(kp, s.psnap, sender_slot) + 1),
    )
    s = s._replace(
        next=_set1(s.next, sender_slot, nn, h_ss & in_snap),
        psnap=_set1(s.psnap, sender_slot, 0, h_ss & in_snap),
        pstate=_set1(s.pstate, sender_slot, P.R_WAIT, h_ss & in_snap),
    )
    return s, eff, r


_FAMILY_HANDLERS = {
    "rep": (_h_replicate,),
    "hb": (_h_heartbeat,),
    "vote": (_h_votereq,),
    "resp": (_h_resp,),
    "any": (_h_replicate, _h_heartbeat, _h_votereq, _h_resp),
}

def _process_family(kp: P.KernelParams, family: str, s: ShardState,
                    eff: Effects, m):
    """One inbound message against one shard, with only ``family``'s
    handlers compiled in — the dispatch-by-type analog of raft.Handle
    (raft.go:1596).  'any' composes every handler (masks are mutually
    exclusive per message type, so composition order cannot change the
    result for a single message)."""
    s, pre = _preamble(kp, s, m)
    r = _empty_resp(s, m, pre)
    for h in _FAMILY_HANDLERS[family]:
        s, eff, r = h(kp, s, eff, m, pre, r)
    return s, eff, r


# ---------------------------------------------------------------------------
# full per-shard step
# ---------------------------------------------------------------------------


def _shard_step(kp: P.KernelParams, s: ShardState, box, inp):
    """Advance one shard one step (vmapped over [G])."""
    E, K, B, RI, Pn = (
        kp.msg_entries, kp.inbox_cap, kp.proposal_cap,
        kp.readindex_cap, kp.num_peers,
    )
    eff = _empty_effects(kp)
    save_base = s.stable  # entries above this are unsaved at step start

    # 0. host-confirmed applied cursor
    s = s._replace(applied=jnp.maximum(s.applied, inp.applied))

    # 0b. device quiesce: a peer's word, and the wake (quiesce.go:60-104,
    # with quiesce.py's QuiesceState as the plain reference).  A group
    # enters together: the replica whose idle clock crosses first says so
    # to its peers in its heartbeat lanes (step 5b; MT.QUIESCE on the
    # wire), and the word is no raft message: its slots are blanked
    # before the handlers run, it carries no term and is no activity.
    # Any non-heartbeat inbound message or client activity (proposal,
    # read, transfer) wakes the lane, resets its idle clock and bumps the
    # wake epoch the quiesce invariants key on.  Heartbeats never count
    # as activity while awake (they must not defer quiesce entry,
    # quiesce.go:64); a quiesced lane answers them asleep during the
    # grace window of e_timeout ticks after its entry (trailing
    # heartbeats of peers not yet in, quiesce.go:84-89) and is woken by
    # one that comes later, so a leader that woke alone brings its group
    # out.  The grace is read off e_tick, which entry zeroes and a
    # quiesced tick advances.  e_tick resets on a wake so a lane whose
    # election clock banked up across quiesced ticks cannot campaign the
    # instant it wakes.
    is_word = (box.mtype == MT.QUIESCE) & (box.from_ != 0)      # [K]
    word = jnp.any(is_word)
    box = jax.tree_util.tree_map(
        lambda x: jnp.where(
            is_word.reshape(is_word.shape + (1,) * (x.ndim - 1)),
            jnp.zeros_like(x), x),
        box)
    hb_like = (box.mtype == MT.HEARTBEAT) | (box.mtype == MT.HEARTBEAT_RESP)
    arrived = box.from_ != 0
    activity = (
        jnp.any(arrived & ~hb_like)
        | jnp.any(inp.prop_valid) | inp.ri_valid | (inp.transfer_to != 0)
    )
    late_hb = jnp.any(arrived & hb_like) & (s.e_tick >= s.e_timeout)
    wake = s.quiesced & (activity | late_hb)
    s = mrep(s, wake, quiesced=False, idle_tick=0, e_tick=0,
             quiesce_epoch=s.quiesce_epoch + 1)

    # 1. inbox processing — slots grouped by their static family
    # (params.slot_families): each family's scan body compiles ONLY that
    # family's handlers, cutting the serial full-matrix cost by ~4x on
    # the router's typed layout (PERF.md lever #1).  'any' slots keep the
    # full matrix for host-staged arbitrary traffic.
    fams = P.slot_families(K)
    order = []
    for fam in ("resp", "rep", "hb", "vote", "any"):
        idxs = [k for k, f in enumerate(fams) if f == fam]
        if idxs:
            order.append((fam, idxs))
    r_parts = []
    for fam, idxs in order:
        if idxs == list(range(K)):
            sub = box
        else:
            gather = jnp.asarray(idxs, I32)
            sub = jax.tree_util.tree_map(lambda a: a[gather], box)

        def _scan_msg(carry, m, _fam=fam):
            s_, eff_ = carry
            s_, eff_, r = _process_family(kp, _fam, s_, eff_, m)
            return (s_, eff_), tuple(r)

        # Rolled by default (unrolling materializes a fresh [G, log_cap]
        # ring copy per slot in the replicate body; measured 11x slower
        # on XLA:CPU, 2026-07-30, where the rolled carry aliases in
        # place — and the hand-restructured merged-family variant that
        # deferred the ring writes measured slower on BOTH platforms, so
        # it was removed in r5).  kp.unroll_scans flips lax.scan's
        # bitwise-neutral unroll flag for the device A/B: on TPU each
        # scan iteration is a separate serial launch of the whole body.
        (s, eff), part = jax.lax.scan(
            _scan_msg, (s, eff), sub,
            unroll=len(idxs) if kp.unroll_scans else 1)
        r_parts.append(part)
    r_stack = tuple(
        jnp.concatenate([p[i] for p in r_parts], axis=0)
        if len(r_parts) > 1 else r_parts[0][i]
        for i in range(7)
    )

    # 2. batched ReadIndex request (node.go:1296 handleReadIndex batches all
    #    queued reads under one ctx; host routes to the leader replica)
    is_leader = s.role == P.LEADER
    ri_req = inp.ri_valid & is_leader
    lt_committed, comp_c, _ = log_term_at(kp, s, s.committed)
    has_cur_term_commit = (sel(comp_c, 0, lt_committed) == s.term) & (s.term > 0)
    single = _is_single_node(s)
    # single-node fast path → ready immediately
    fast = ri_req & single
    lane = jnp.minimum(eff.rtr_n, RI - 1)
    eff = eff._replace(
        rtr_valid=_set1(eff.rtr_valid, lane, True, fast),
        rtr_index=_set1(eff.rtr_index, lane, s.committed, fast),
        rtr_low=_set1(eff.rtr_low, lane, inp.ri_low, fast),
        rtr_high=_set1(eff.rtr_high, lane, inp.ri_high, fast),
        rtr_n=eff.rtr_n + sel(fast, 1, 0),
    )
    quorum_path = ri_req & ~single & has_cur_term_commit
    s, dropped_full = _ri_push(kp, s, quorum_path, inp.ri_low, inp.ri_high,
                               s.committed)
    eff = eff._replace(
        need_hb=eff.need_hb | (quorum_path & ~dropped_full),
        hb_low=sel(quorum_path, inp.ri_low, eff.hb_low),
        hb_high=sel(quorum_path, inp.ri_high, eff.hb_high),
        ri_dropped=eff.ri_dropped
        | (inp.ri_valid & (~is_leader | (ri_req & ~single & ~has_cur_term_commit)))
        | dropped_full,
    )

    # 3. proposals (leader only, not while transferring; raft.go:1794)
    can_prop = is_leader & (s.ltt == 0)

    prop_vals = (inp.prop_val if inp.prop_val is not None
                 else jnp.zeros_like(inp.prop_cc, I32))

    # Closed-form batch append — this was a B-iteration lax.scan, and
    # serial loops are poison on TPU (every iteration is its own tiny
    # launch over the whole [G] state).  The scan's slot-order semantics
    # are reproduced exactly:
    #  - ring-capacity guard: `last` advances per accept and the room
    #    check is monotone within a batch, so capping the accept RANK at
    #    the remaining room cuts the same suffix the per-slot check did
    #    (host sees prop_accepted=False → system busy; compaction frees
    #    space — the reference's in-mem log rate limiting);
    #  - one-at-a-time config change: only the first CC candidate lands
    #    while none is pending; later CCs in the batch drop.
    v0 = inp.prop_valid & can_prop                           # [B]
    cc_cand = v0 & inp.prop_cc & ~s.pending_cc
    cc_first = cc_cand & (jnp.cumsum(cc_cand.astype(I32)) == 1)
    do1 = v0 & (~inp.prop_cc | cc_first)
    m_max = kp.log_cap - (s.last - s.snap_index)             # ring room left
    do = do1 & (jnp.cumsum(do1.astype(I32)) <= m_max)
    rank = jnp.cumsum(do.astype(I32))                        # 1-based
    n_total = rank[-1]
    appended_any = n_total > 0
    prop_accepted = do
    prop_index = sel(do, s.last + rank, 0)
    prop_term = sel(do, jnp.broadcast_to(s.term, do.shape), 0)
    # compress accepted slots by rank: off j holds (is_cc, val) of the
    # rank-(j+1) accept — the ring write below reads by offset
    B = do.shape[0]
    rank_onehot = (rank[None, :] == (jnp.arange(B, dtype=I32) + 1)[:, None]) \
        & do[None, :]                                        # [B(off), B(slot)]
    cc_by_off = jnp.any(rank_onehot & cc_first[None, :], axis=1)
    val_by_off = jnp.sum(rank_onehot * prop_vals[None, :], axis=1)
    # one pass over the ring: position p hosts unwrapped index base+off;
    # n_total <= B << log_cap, so the append window never self-wraps
    base = s.last + 1
    pos = jnp.arange(kp.log_cap, dtype=I32)
    off = (pos - _slot(kp, base)) & (kp.log_cap - 1)
    in_win = off < n_total
    off_c = jnp.minimum(off, B - 1)
    # [CAP]-indexed reads of the [B] by-offset tables: _get1 handles the
    # vector index (one-hot [CAP, B] on device, gather on CPU)
    s = s._replace(
        lt=sel(in_win, jnp.broadcast_to(s.term, pos.shape), s.lt),
        lcc=sel(in_win, _get1(kp, cc_by_off, off_c), s.lcc),
        last=s.last + n_total,
        pending_cc=s.pending_cc | jnp.any(do & cc_first),
    )
    if kp.inline_payloads:
        s = s._replace(lv=sel(in_win, _get1(kp, val_by_off, off_c), s.lv))
    eff = eff._replace(save_from=sel(
        appended_any, jnp.minimum(eff.save_from, base), eff.save_from))
    self_mask = _self_slot_mask(s)
    s = s._replace(
        match=sel(appended_any & self_mask, s.last, s.match),
        next=sel(appended_any & self_mask, s.last + 1, s.next),
    )
    s = jax.tree_util.tree_map(
        lambda a, b: sel(appended_any & single, a, b), _try_commit(kp, s), s
    )
    eff = eff._replace(need_rep=sel(appended_any, jnp.ones_like(eff.need_rep),
                                    eff.need_rep))

    # 4. leadership transfer request (raft.go:1925 handleLeaderTransfer)
    tr = inp.transfer_to
    tr_req = (tr != 0) & is_leader & (s.ltt == 0) & (tr != s.replica_id)
    tr_hit = (s.pid == tr) & (s.kind == P.K_VOTER)
    tr_known = jnp.any(tr_hit)
    tr_slot = jnp.argmax(tr_hit)
    do_tr = tr_req & tr_known
    s = mrep(s, do_tr, ltt=tr, e_tick=0)
    fast_tn = do_tr & (_get1(kp, s.match, tr_slot) == s.last)
    eff = eff._replace(send_tn=_set1(eff.send_tn, tr_slot, True, fast_tn))

    # 5. tick (raft.go:571-655)
    is_leader = s.role == P.LEADER  # refresh (campaigns can't happen above)
    # the quiesced mask is the union of the host-driven input flag and
    # the device-resident mask (post-wake, so an activity step ticks live)
    q_any = inp.quiesced | s.quiesced
    live_tick = inp.tick & ~q_any
    # quiesced tick: just advance the election clock
    s = mrep(s, inp.tick & q_any, e_tick=s.e_tick + 1)
    # non-leader tick
    nl = live_tick & ~is_leader
    s = mrep(s, nl, e_tick=s.e_tick + 1)
    can_campaign = (
        (s.role == P.FOLLOWER) | (s.role == P.CANDIDATE)
        | (s.role == P.PRE_VOTE_CANDIDATE)
    )
    elect = nl & can_campaign & (s.e_tick >= s.rand_timeout)
    s = mrep(s, elect, e_tick=0)
    s, eff = _campaign(kp, s, eff, elect)
    # leader tick
    lt_ = live_tick & is_leader
    s = mrep(s, lt_, e_tick=s.e_tick + 1)
    cq_time = lt_ & (s.e_tick >= s.e_timeout)
    abort_tr = cq_time & (s.ltt != 0)
    s = mrep(s, cq_time, e_tick=0)
    # checkQuorum (raft.go:1785): count active voters (self counts), reset
    do_cq = cq_time & s.check_quorum
    active_v = jnp.sum(
        (_voting_mask(s) & (s.active | _self_slot_mask(s))).astype(I32)
    )
    lost = do_cq & (active_v < _quorum(s))
    s = s._replace(active=sel(do_cq, jnp.zeros_like(s.active), s.active))
    s = _become_follower(s, lost, s.term, 0)
    s = mrep(s, abort_tr & ~lost, ltt=0)
    is_leader = s.role == P.LEADER
    lt_ = lt_ & is_leader
    s = mrep(s, lt_, h_tick=s.h_tick + 1)
    hb_time = lt_ & (s.h_tick >= s.h_timeout)
    s = mrep(s, hb_time, h_tick=0)
    # heartbeat broadcast uses the newest pending RI ctx (raft.go:849)
    RIm = kp.readindex_cap - 1
    newest = (s.ri_head + s.ri_count - 1) & RIm
    has_pending = s.ri_count > 0
    eff = eff._replace(
        need_hb=eff.need_hb | hb_time,
        hb_low=sel(hb_time, sel(has_pending, _get1(kp, s.ri_low, newest), 0),
                   eff.hb_low),
        hb_high=sel(hb_time, sel(has_pending, _get1(kp, s.ri_high, newest), 0),
                    eff.hb_high),
    )

    # 5b. device quiesce idle clock + entry (quiesce.go:43-54 tick,
    # :96-104 tryEnterQuiesce): an enabled, awake lane idle for
    # e_timeout*10 ticks (quiesce.py threshold) raises its quiesced mask
    # and tells its peers (the send phase puts the word in its heartbeat
    # lanes); an awake lane that hears a peer's word follows it.  A tick
    # is a round of the lane's OWN engine and a group's three engines
    # step at different rates, so the receiver is not held to upstream's
    # wall-clock rule (no entry within threshold ticks of its last exit:
    # under clocks a few percent apart that turns every re-entry into an
    # election, the follower refusing the word of a leader that then
    # goes silent): it follows the word once its own idle clock is half
    # way, which a lane that just left quiesce, or just served anything,
    # is not.  Entry clears both protocol clocks so neither an election
    # nor a heartbeat fires mid-quiesce, and idle_tick stays where entry
    # found it (at the threshold: own clock; under it: a peer's word).
    # Entry is evaluated AFTER this step's tick work, so the step that
    # crosses the threshold still ran live — the mask only gates future
    # steps, and the kernel stays bitwise-identical with quiesce_on off.
    s = mrep(s, inp.tick & ~activity & ~s.quiesced,
             idle_tick=s.idle_tick + 1)
    s = mrep(s, activity, idle_tick=0)
    q_threshold = s.e_timeout * 10
    enter_own = (s.quiesce_on & ~s.quiesced & inp.tick
                 & (s.idle_tick >= q_threshold))
    enter_peer = (s.quiesce_on & ~s.quiesced & word
                  & (s.idle_tick * 2 >= q_threshold))
    s = mrep(s, enter_own | enter_peer, quiesced=True, e_tick=0, h_tick=0)

    # 6. send phase ------------------------------------------------------
    is_leader = s.role == P.LEADER
    not_self = ~_self_slot_mask(s)
    present = s.kind != P.K_ABSENT

    # replicate lanes (sendReplicateMessage; raft.go:800)
    want_rep = eff.need_rep & is_leader & present & not_self
    pausedP = (s.pstate == P.R_WAIT) | (s.pstate == P.R_SNAPSHOT)
    can_send = want_rep & ~pausedP
    prev = s.next - 1
    prev_term, prev_comp, _ = jax.vmap(lambda i: log_term_at(kp, s, i))(prev)
    needs_snap = can_send & prev_comp  # log compacted under the peer
    # witness peers take a file-less stripped snapshot the host can
    # build from the recorded snapshot directly (raft.go:720-735) — no
    # stream, no eviction; only non-witness peers escalate
    wit_snap = needs_snap & (s.kind == P.K_WITNESS)
    send_rep = can_send & ~prev_comp
    n_avail = jnp.clip(s.last - prev, 0, E)
    lane = jnp.arange(E, dtype=I32)
    ent_idx = s.next[:, None] + lane[None, :]          # [P, E]
    ent_live = lane[None, :] < n_avail[:, None]
    eslot = _slot(kp, ent_idx)
    ent_term = sel(ent_live, _get1(kp, s.lt, eslot), 0)
    ent_cc = sel(ent_live, _get1(kp, s.lcc, eslot), False)
    ent_val = (sel(ent_live, _get1(kp, s.lv, eslot), 0)
               if kp.inline_payloads else None)
    # optimistic pipelined advance (remote.go:progress)
    adv = send_rep & (s.pstate == P.R_REPLICATE) & (n_avail > 0)
    s = s._replace(
        next=sel(adv, s.next + n_avail, s.next),
        pstate=sel(send_rep & (s.pstate == P.R_RETRY), P.R_WAIT,
                   sel(needs_snap, P.R_SNAPSHOT, s.pstate)),
        psnap=sel(needs_snap, s.snap_index, s.psnap),
    )
    s = mrep(s, jnp.any(needs_snap & ~wit_snap), needs_host=True)

    # heartbeat lanes (broadcastHeartbeatMessageWithHint; raft.go:859-871)
    has_ctx = (eff.hb_low != 0) | (eff.hb_high != 0)
    hb_target = present & not_self & (
        _voting_mask(s) | (~has_ctx & (s.kind == P.K_NON_VOTING))
    )
    send_hb = eff.need_hb & is_leader & hb_target
    hb_commit = jnp.minimum(s.match, s.committed)
    # the word of a lane that entered quiesce on its own clock rides the
    # same lanes, to every peer whatever its kind, marked by a commit no
    # heartbeat carries (params.QUIESCE_WORD); it replaces a heartbeat
    # due in the same step, which the entry makes moot
    send_hb = sel(enter_own, present & not_self, send_hb)
    hb_commit = sel(enter_own, P.QUIESCE_WORD, hb_commit)

    # vote-request lanes — masked by END-OF-STEP role: a campaign started
    # earlier in the step may have been cancelled by a later message (e.g.
    # a higher-term Replicate folded us back to follower); only a live
    # candidate may broadcast at its current term
    role_ok = sel(eff.send_vote == 2, s.role == P.PRE_VOTE_CANDIDATE,
                  s.role == P.CANDIDATE)
    vr = (eff.send_vote > 0) & role_ok & _voting_mask(s) & not_self
    vote_term = sel(eff.send_vote == 2, s.term + 1, s.term)
    last_t, _, _ = log_term_at(kp, s, s.last)

    # persistence: entries (save_first..save_last] inclusive-of-first form
    save_first = sel(eff.save_from == INT_MAX, save_base + 1,
                     jnp.minimum(eff.save_from, save_base + 1))
    save_last = s.last
    s = s._replace(stable=jnp.maximum(save_last, 0))

    # apply release (pagination per logentry.go:268)
    apply_first = s.processed + 1
    apply_last = jnp.minimum(s.committed, s.processed + kp.apply_batch)
    s = s._replace(processed=jnp.maximum(s.processed, apply_last))

    # device-side log compaction — the ring analog of removeLog()
    # (node.go:803): raise the snapshot floor over entries that are applied
    # everywhere we care about, keeping compaction_overhead entries for
    # laggards (config.CompactionOverhead). A leader also retains anything
    # a present peer still needs (min match).
    peer_floor = jnp.min(
        sel(
            (s.kind != P.K_ABSENT) & ~_self_slot_mask(s),
            s.match, INT_MAX,
        )
    )
    floor = jnp.minimum(s.applied, s.committed)
    floor = sel(is_leader, jnp.minimum(floor, peer_floor), floor)
    new_snap = jnp.maximum(
        s.snap_index, floor - kp.compaction_overhead
    )
    new_snap_term, nsc, nsu = log_term_at(kp, s, new_snap)
    can_compact = (new_snap > s.snap_index) & ~nsc & ~nsu
    s = mrep(s, can_compact, snap_index=new_snap, snap_term=new_snap_term)

    out = StepOutput(
        r_type=r_stack[0], r_to=r_stack[1], r_term=r_stack[2],
        r_log_index=r_stack[3], r_reject=r_stack[4], r_hint=r_stack[5],
        r_hint_high=r_stack[6],
        s_rep=send_rep, s_prev_index=prev, s_prev_term=sel(prev_comp, 0, prev_term),
        s_commit=jnp.broadcast_to(s.committed, (Pn,)),
        s_n_ent=sel(send_rep, n_avail, 0),
        s_ent_term=ent_term, s_ent_cc=ent_cc, s_ent_val=ent_val,
        s_vote=sel(vr, eff.send_vote, 0),
        s_vote_term=jnp.broadcast_to(vote_term, (Pn,)),
        s_vote_lindex=jnp.broadcast_to(s.last, (Pn,)),
        s_vote_lterm=jnp.broadcast_to(last_t, (Pn,)),
        s_vote_hint=jnp.broadcast_to(eff.vote_hint, (Pn,)),
        s_hb=send_hb, s_hb_commit=hb_commit,
        s_hb_low=jnp.broadcast_to(eff.hb_low, (Pn,)),
        s_hb_high=jnp.broadcast_to(eff.hb_high, (Pn,)),
        s_timeout_now=eff.send_tn & is_leader,
        s_need_snapshot=needs_snap & ~wit_snap,
        s_wit_snap=wit_snap,
        save_first=save_first, save_last=save_last,
        apply_first=apply_first, apply_last=apply_last,
        term=s.term, vote=s.vote, commit=s.committed,
        rtr_valid=eff.rtr_valid, rtr_index=eff.rtr_index,
        rtr_low=eff.rtr_low, rtr_high=eff.rtr_high,
        ri_dropped=eff.ri_dropped,
        prop_accepted=prop_accepted, prop_index=prop_index, prop_term=prop_term,
        leader=s.leader, leader_term=s.term,
        needs_host=s.needs_host,
    )
    return s, out


@functools.partial(jax.jit, static_argnums=0)
def step(kp: P.KernelParams, state: ShardState, inbox: Inbox,
         inp: StepInput) -> tuple[ShardState, StepOutput]:
    """vmap the per-shard step across the [G] axis and jit the result."""
    return jax.vmap(functools.partial(_shard_step, kp))(state, inbox, inp)


# Donated entry point for the pipelined engine loop: identical math to
# ``step``, but XLA may reuse the state/inbox/input buffers for the
# outputs instead of allocating fresh SoA arrays every step.  The host
# contract this implies is declared in kstate.DONATION and cross-checked
# by analysis/contracts.py (KC008): after a step_donated dispatch the
# caller must treat the donated arrays as dead — every host read goes
# through the RETURNED state or the host mirrors, never the arguments.
# Backends without donation support (CPU) fall back to copying; the
# engine keeps the same no-touch discipline on all backends so the
# differential oracle covers the strict contract.
@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2, 3))
def step_donated(kp: P.KernelParams, state: ShardState, inbox: Inbox,
                 inp: StepInput) -> tuple[ShardState, StepOutput]:
    """``step`` with state/inbox/input buffers donated to XLA."""
    return jax.vmap(functools.partial(_shard_step, kp))(state, inbox, inp)


@jax.jit
def output_row_flags(outs) -> jnp.ndarray:
    """[G, C] bool: per-row any() over each message class of a StepOutput,
    column order ``kstate.FLAG_CLASSES``.  The round's program (core/round.py)
    writes it into the leading columns of the packed download, where the
    engine reads which message classes a row has at all."""
    cols = (
        jnp.any(outs.r_type != 0, axis=1),
        jnp.any(outs.s_rep, axis=1),
        jnp.any(outs.s_hb, axis=1),
        jnp.any(outs.s_vote != 0, axis=1),
        jnp.any(outs.s_timeout_now, axis=1),
        jnp.any(outs.s_need_snapshot, axis=1),
        jnp.any(outs.s_wit_snap, axis=1),
        jnp.any(outs.rtr_valid, axis=1),
    )
    return jnp.stack(cols, axis=1)

"""Device-side message router for co-located replica groups.

The reference exchanges messages over TCP (internal/transport) or an
in-process chan transport (plugin/chan).  When all replicas of a group live
in the same kernel state (the single-host / single-slice case — BASELINE
configs #2-#4), message exchange is a pure array shuffle: out-lanes of step
t become in-lanes of step t+1 with no host involvement.  This module builds
that shuffle with gathers over a ``[N, R, ...]`` (groups × replicas) view —
the same pattern later extends across chips with collective permutes.

Inbox slot layout per target, per peer q of the R-1 remote peers:
  [q*5 + 0]  first response lane addressed to me
  [q*5 + 1]  second response lane addressed to me
  [q*5 + 2]  replicate
  [q*5 + 3]  heartbeat
  [q*5 + 4]  vote request / TimeoutNow (mutually exclusive senders)
Requires ``inbox_cap >= 5 * (R - 1)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.core import params as KP
from dragonboat_tpu.core.kstate import Inbox, ShardState, StepInput, StepOutput
from dragonboat_tpu.core.kernel import onehot_select, step

MT = pb.MessageType
I32 = jnp.int32

# ---------------------------------------------------------------------------
# slot-layout helpers (shared, PUBLIC): the host-side fallback stager
# (engine/kernel_engine._InboxBuilder in mesh mode) must place a
# hub-delivered message in EXACTLY the slot route() would have used, or
# the device-resident and hub-fallback delivery paths stop being bitwise
# interchangeable (tests/test_engine_differential.py third arm).  Every
# piece of layout arithmetic lives here so the two sides cannot drift.
# ---------------------------------------------------------------------------

#: slots per remote peer in the fixed inbox layout (module docstring)
SLOTS_PER_PEER = 5
#: class offsets within one peer's slot block
SLOT_RESP0, SLOT_RESP1, SLOT_REP, SLOT_HB, SLOT_VOTE = range(SLOTS_PER_PEER)

#: route()-producible message types -> slot offset within the peer block.
#: Responses get two lanes (SLOT_RESP0 then SLOT_RESP1); the vote slot is
#: shared by mutually-exclusive senders (a replica never sends a vote
#: request AND TimeoutNow in one step).
SLOT_OFFSETS_OF_TYPE = {
    int(MT.REPLICATE): (SLOT_REP,),
    int(MT.HEARTBEAT): (SLOT_HB,),
    int(MT.QUIESCE): (SLOT_HB,),
    int(MT.REQUEST_VOTE): (SLOT_VOTE,),
    int(MT.REQUEST_PREVOTE): (SLOT_VOTE,),
    int(MT.TIMEOUT_NOW): (SLOT_VOTE,),
}
# everything else (responses and host-originated kernel messages such as
# UNREACHABLE / SNAPSHOT_STATUS) rides the response lanes
_RESP_OFFSETS = (SLOT_RESP0, SLOT_RESP1)


def peer_ordinal(target_rid: int, source_rid: int, replicas: int) -> int:
    """Remote-peer ordinal ``q`` of ``source_rid`` as seen by
    ``target_rid``: the inverse of route()'s source enumeration
    ``s = (t + 1 + q) % R`` (both rids 1-based, q in 0..R-2)."""
    return (source_rid - target_rid - 1) % replicas


def slot_candidates(target_rid: int, source_rid: int, replicas: int,
                    mtype: int) -> tuple[int, ...]:
    """Inbox slot indexes (in preference order) where route() would place
    a ``mtype`` message from ``source_rid`` addressed to ``target_rid``."""
    base = peer_ordinal(target_rid, source_rid, replicas) * SLOTS_PER_PEER
    offs = SLOT_OFFSETS_OF_TYPE.get(int(mtype), _RESP_OFFSETS)
    return tuple(base + o for o in offs)


def route(kp: KP.KernelParams, replicas: int, out: StepOutput) -> Inbox:
    """Turn one step's StepOutput into the next step's Inbox, fully on device.

    All arrays have leading [G] = [N*R] with rows grouped by raft group.
    """
    R = replicas
    K, E = kp.inbox_cap, kp.msg_entries
    assert K >= SLOTS_PER_PEER * (R - 1), \
        "inbox_cap too small for the fixed slot layout"
    G = out.term.shape[0]
    N = G // R

    def grp(x):  # [G, ...] -> [N, R, ...]
        return x.reshape((N, R) + x.shape[1:])

    term = grp(out.term)

    # --- response lanes: for each (target t, source s) pick up to 2 resp
    # lanes addressed to t ------------------------------------------------
    r_type = grp(out.r_type)          # [N, R, K]
    r_to = grp(out.r_to)
    r_term = grp(out.r_term)
    r_log_index = grp(out.r_log_index)
    r_reject = grp(out.r_reject)
    r_hint = grp(out.r_hint)
    r_hint_high = grp(out.r_hint_high)

    # to_me[t, s, k]: source s's resp lane k addresses replica t+1
    rid_t = jnp.arange(1, R + 1, dtype=I32)                  # [R]
    to_me = (r_to[:, None, :, :] == rid_t[None, :, None, None]) & (
        r_type[:, None, :, :] != 0
    )                                                        # [N, Rt, Rs, K]
    # first and second matching lane indexes per (t, s)
    lane_iota = jnp.arange(K, dtype=I32)
    big = jnp.asarray(K, I32)
    lane_or_big = jnp.where(to_me, lane_iota, big)
    first = jnp.min(lane_or_big, axis=-1)                    # [N, Rt, Rs]
    lane_or_big2 = jnp.where(
        to_me & (lane_iota != first[..., None]), lane_iota, big
    )
    second = jnp.min(lane_or_big2, axis=-1)

    def pick(src_field, lane):  # src_field [N, Rs, K] ; lane [N, Rt, Rs]
        if not kp.onehot_reads:
            sf = jnp.broadcast_to(src_field[:, None], (N, R, R, K))
            return jnp.take_along_axis(
                sf, jnp.minimum(lane, K - 1)[..., None], axis=-1
            )[..., 0]
        # one-hot select instead of take_along_axis: a batched gather
        # serializes over the batch axis on TPU (see kernel._get1); a
        # lane==K sentinel has no hot slot and reads 0/False, which the
        # caller's validity mask discards either way (the gather branch
        # clamps the sentinel to K-1 under the same mask)
        oh = lane[..., None] == lane_iota                     # [N,Rt,Rs,K]
        return onehot_select(oh, src_field[:, None], -1)

    resp_valid1 = first < K
    resp_valid2 = second < K

    # --- per-peer lanes: source s's peer-slot (t) lanes --------------------
    # peer slot index for target rid t+1 is t (pid layout [1..R])
    def peer_lane(field):  # [N, Rs, P(, E)] -> [N, Rt, Rs(, E)]
        f = grp(field)                                       # [N, Rs, P, ...]
        sl = f[:, :, :R]                                     # peer slots 0..R-1
        return jnp.swapaxes(sl, 1, 2)                        # [N, Rt, Rs, ...]

    rep_valid = peer_lane(out.s_rep)
    rep_prev_i = peer_lane(out.s_prev_index)
    rep_prev_t = peer_lane(out.s_prev_term)
    rep_commit = peer_lane(out.s_commit)
    rep_n = peer_lane(out.s_n_ent)
    rep_ent_t = peer_lane(out.s_ent_term)                    # [N, Rt, Rs, E]
    rep_ent_cc = peer_lane(out.s_ent_cc)
    inline = out.s_ent_val is not None
    rep_ent_v = peer_lane(out.s_ent_val) if inline else None
    hb_valid = peer_lane(out.s_hb)
    hb_commit = peer_lane(out.s_hb_commit)
    hb_low = peer_lane(out.s_hb_low)
    hb_high = peer_lane(out.s_hb_high)
    vt_kind = peer_lane(out.s_vote)                          # 0/1/2
    vt_term = peer_lane(out.s_vote_term)
    vt_li = peer_lane(out.s_vote_lindex)
    vt_lt = peer_lane(out.s_vote_lterm)
    vt_hint = peer_lane(out.s_vote_hint)
    tn_valid = peer_lane(out.s_timeout_now)

    src_term = jnp.broadcast_to(term[:, None, :], (N, R, R))  # [N, Rt, Rs]
    src_rid = jnp.broadcast_to(
        jnp.arange(1, R + 1, dtype=I32)[None, None, :], (N, R, R)
    )

    # --- assemble the [N, Rt, K] inbox ------------------------------------
    fields = {
        "mtype": jnp.zeros((N, R, K), I32),
        "from_": jnp.zeros((N, R, K), I32),
        "term": jnp.zeros((N, R, K), I32),
        "log_term": jnp.zeros((N, R, K), I32),
        "log_index": jnp.zeros((N, R, K), I32),
        "commit": jnp.zeros((N, R, K), I32),
        "reject": jnp.zeros((N, R, K), bool),
        "hint": jnp.zeros((N, R, K), I32),
        "hint_high": jnp.zeros((N, R, K), I32),
        "n_ent": jnp.zeros((N, R, K), I32),
        "ent_term": jnp.zeros((N, R, K, E), I32),
        "ent_cc": jnp.zeros((N, R, K, E), bool),
    }
    if inline:
        fields["ent_val"] = jnp.zeros((N, R, K, E), I32)

    # enumerate the R-1 remote sources for each target: s = (t + 1 + q) % R
    t_iota = jnp.arange(R, dtype=I32)
    for q in range(R - 1):
        s_of_t = (t_iota + 1 + q) % R                        # [R]

        # one-hot over the (small, static) source axis — see pick()
        oh_src = s_of_t[:, None] == jnp.arange(R, dtype=I32)  # [Rt, Rs]

        def take(x3):  # [N, Rt, Rs] select source s_of_t[t]
            if not kp.onehot_reads:
                idx = jnp.broadcast_to(s_of_t[None, :, None], (N, R, 1))
                return jnp.take_along_axis(x3, idx, axis=2)[:, :, 0]
            return onehot_select(oh_src[None], x3, 2)

        def take4(x4):  # [N, Rt, Rs, E]
            if not kp.onehot_reads:
                idx = jnp.broadcast_to(
                    s_of_t[None, :, None, None], (N, R, 1, x4.shape[-1]))
                return jnp.take_along_axis(x4, idx, axis=2)[:, :, 0]
            return onehot_select(oh_src[None, :, :, None], x4, 2)

        base = q * SLOTS_PER_PEER
        # responses
        for lane_no, (lane, vmask) in enumerate(
            ((first, resp_valid1), (second, resp_valid2))
        ):
            v = take(vmask)
            k_slot = base + lane_no
            fields["mtype"] = fields["mtype"].at[:, :, k_slot].set(
                jnp.where(v, take(pick(r_type, lane)), 0))
            fields["from_"] = fields["from_"].at[:, :, k_slot].set(
                jnp.where(v, take(src_rid), 0))
            fields["term"] = fields["term"].at[:, :, k_slot].set(
                jnp.where(v, take(pick(r_term, lane)), 0))
            fields["log_index"] = fields["log_index"].at[:, :, k_slot].set(
                jnp.where(v, take(pick(r_log_index, lane)), 0))
            fields["reject"] = fields["reject"].at[:, :, k_slot].set(
                jnp.where(v, take(pick(r_reject, lane)).astype(bool), False))
            fields["hint"] = fields["hint"].at[:, :, k_slot].set(
                jnp.where(v, take(pick(r_hint, lane)), 0))
            fields["hint_high"] = fields["hint_high"].at[:, :, k_slot].set(
                jnp.where(v, take(pick(r_hint_high, lane)), 0))
        # replicate
        v = take(rep_valid)
        k_slot = base + SLOT_REP
        fields["mtype"] = fields["mtype"].at[:, :, k_slot].set(
            jnp.where(v, MT.REPLICATE, 0))
        fields["from_"] = fields["from_"].at[:, :, k_slot].set(
            jnp.where(v, take(src_rid), 0))
        fields["term"] = fields["term"].at[:, :, k_slot].set(
            jnp.where(v, take(src_term), 0))
        fields["log_term"] = fields["log_term"].at[:, :, k_slot].set(
            jnp.where(v, take(rep_prev_t), 0))
        fields["log_index"] = fields["log_index"].at[:, :, k_slot].set(
            jnp.where(v, take(rep_prev_i), 0))
        fields["commit"] = fields["commit"].at[:, :, k_slot].set(
            jnp.where(v, take(rep_commit), 0))
        fields["n_ent"] = fields["n_ent"].at[:, :, k_slot].set(
            jnp.where(v, take(rep_n), 0))
        fields["ent_term"] = fields["ent_term"].at[:, :, k_slot].set(
            jnp.where(v[..., None], take4(rep_ent_t), 0))
        fields["ent_cc"] = fields["ent_cc"].at[:, :, k_slot].set(
            jnp.where(v[..., None], take4(rep_ent_cc), False))
        if inline:
            fields["ent_val"] = fields["ent_val"].at[:, :, k_slot].set(
                jnp.where(v[..., None], take4(rep_ent_v), 0))
        # heartbeat
        v = take(hb_valid)
        k_slot = base + SLOT_HB
        # a heartbeat lane whose commit is the quiesce word carries that
        # word, not a heartbeat (params.QUIESCE_WORD; the kernel reads
        # nothing but the slot's type and sender)
        fields["mtype"] = fields["mtype"].at[:, :, k_slot].set(
            jnp.where(v, jnp.where(take(hb_commit) == KP.QUIESCE_WORD,
                                   MT.QUIESCE, MT.HEARTBEAT), 0))
        fields["from_"] = fields["from_"].at[:, :, k_slot].set(
            jnp.where(v, take(src_rid), 0))
        fields["term"] = fields["term"].at[:, :, k_slot].set(
            jnp.where(v, take(src_term), 0))
        fields["commit"] = fields["commit"].at[:, :, k_slot].set(
            jnp.where(v, take(hb_commit), 0))
        fields["hint"] = fields["hint"].at[:, :, k_slot].set(
            jnp.where(v, take(hb_low), 0))
        fields["hint_high"] = fields["hint_high"].at[:, :, k_slot].set(
            jnp.where(v, take(hb_high), 0))
        # vote request or TimeoutNow
        vk = take(vt_kind)
        tn = take(tn_valid)
        k_slot = base + SLOT_VOTE
        mt = jnp.where(
            tn, MT.TIMEOUT_NOW,
            jnp.where(vk == 1, MT.REQUEST_VOTE,
                      jnp.where(vk == 2, MT.REQUEST_PREVOTE, 0)),
        )
        v = mt != 0
        fields["mtype"] = fields["mtype"].at[:, :, k_slot].set(mt)
        fields["from_"] = fields["from_"].at[:, :, k_slot].set(
            jnp.where(v, take(src_rid), 0))
        fields["term"] = fields["term"].at[:, :, k_slot].set(
            jnp.where(tn, take(src_term), jnp.where(v, take(vt_term), 0)))
        fields["log_index"] = fields["log_index"].at[:, :, k_slot].set(
            jnp.where(vk > 0, take(vt_li), 0))
        fields["log_term"] = fields["log_term"].at[:, :, k_slot].set(
            jnp.where(vk > 0, take(vt_lt), 0))
        fields["hint"] = fields["hint"].at[:, :, k_slot].set(
            jnp.where(vk > 0, take(vt_hint), 0))

    return Inbox(**{k: v.reshape((G,) + v.shape[2:]) for k, v in fields.items()})


@functools.partial(jax.jit, static_argnums=(0, 1))
def cluster_step(kp: KP.KernelParams, replicas: int, state: ShardState,
                 inbox: Inbox, inp: StepInput):
    """One fused step for co-located groups: kernel step + device routing.

    Returns (state, next_inbox, out).  The host only reads the slim result
    lanes it needs (prop fates, rtr lanes, save/apply cursors)."""
    state, out = step(kp, state, inbox, inp)
    nxt = route(kp, replicas, out)
    return state, nxt, out


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3, 4))
def cluster_step_donated(kp: KP.KernelParams, replicas: int,
                         state: ShardState, inbox: Inbox, inp: StepInput):
    """Donating twin of ``cluster_step`` (kstate.DONATION
    ``cluster_step_donated``): state, inbox and input hand their buffers
    to XLA, so after dispatch the caller must only read the RETURNED
    state/inbox/out — the depth-1 differential arm's retire-before-
    dispatch order (tests/test_engine_differential.py) upholds that."""
    state, out = step(kp, state, inbox, inp)
    nxt = route(kp, replicas, out)
    return state, nxt, out

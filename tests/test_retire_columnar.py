"""The retire pass by columns (``KernelEngine._process_outputs``) against a
plain per-cell reference.

A scene is a hand-made ``[G, Wd] int32`` download (written through
``kstate``'s column table) over a few lanes with stand-in nodes.  The engine
retires it; ``_reference`` below retires the same download a lane at a time
and a cell at a time (``down[g, col.start + offset]``, nothing of the engine
imported) against a second, equal set of nodes.  Both leave an ordered log
(sends, the save, applies, futures completed, events, evictions) and their
nodes' mirrors; the test compares: the same messages per (shard, target) in
the same order, the same updates, the same applies, replicates before the
save and every other message after it."""

from __future__ import annotations

import copy
import itertools
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from dragonboat_tpu import raftpb as pb, telemetry
from dragonboat_tpu.config import MeshSpec
from dragonboat_tpu.core import kstate, params as KP
from dragonboat_tpu.engine import kernel_engine as ke
from dragonboat_tpu.request import RequestResultCode

MT = pb.MessageType
G = 12
KPARAMS = KP.KernelParams(num_peers=3, log_cap=64, inbox_cap=4,
                          msg_entries=8, proposal_cap=4, readindex_cap=2,
                          save_window=4)
COLS = kstate.round_columns(KPARAMS)
COL = {c.field: c for c in COLS.down}
FLAG = {c: i for i, c in enumerate(kstate.FLAG_CLASSES)}
S = COLS.save_window
VOTER, WITNESS = KP.K_VOTER, KP.K_WITNESS


# -- stand-in nodes -------------------------------------------------------------


class Book:
    """Proposal futures by key: what the engine may do to one."""

    def __init__(self, log, who, keys=()):
        self.log, self.who, self.keys = log, who, set(keys)

    def idle(self):
        return not self.keys

    def applied(self, key, client_id, series_id, result, rejected):
        if key in self.keys:
            self.keys.discard(key)
            self.log.append(("acked", self.who, key, rejected))

    def committed(self, key):
        if key in self.keys:
            self.log.append(("commit-notified", self.who, key))

    def dropped(self, key):
        if key in self.keys:
            self.keys.discard(key)
            self.log.append(("dropped", self.who, key))


class Reads:
    def __init__(self, log, who, pending=()):
        self.log, self.who = log, who
        self.pending = set(pending)     # ctx.low of staged batches
        self.waiting = []               # (index, low)

    def add_ready(self, ctx, index):
        if ctx.low in self.pending:
            self.pending.discard(ctx.low)
            self.waiting.append((index, ctx.low))

    def applied(self, index):
        done = [w for w in self.waiting if w[0] <= index]
        self.waiting = [w for w in self.waiting if w[0] > index]
        for _i, low in done:
            self.log.append(("read-served", self.who, low))

    def dropped(self, ctx):
        if ctx.low in self.pending:
            self.pending.discard(ctx.low)
            self.log.append(("read-dropped", self.who, ctx.low))


class SM:
    def __init__(self, log, who, last_applied):
        self.log, self.who, self.last_applied = log, who, last_applied

    def get_last_applied(self):
        return self.last_applied

    def handle(self, entries):
        self.log.append(("apply", self.who, tuple(entries)))
        out = []
        for e in entries:
            if e.index <= self.last_applied:
                continue        # an on-disk state machine's replay skip
            self.last_applied = e.index
            out.append(SimpleNamespace(
                index=e.index, key=e.key, client_id=e.client_id,
                series_id=e.series_id, result=e.index, rejected=False))
        return out


class LogDB:
    def __init__(self, log, snapshot=None):
        self.log, self.snapshot = log, snapshot

    def save_raft_state(self, updates, worker_id=0):
        self.log.append(("save", tuple(updates)))

    def get_snapshot(self, shard_id, replica_id):
        return self.snapshot


def make_node(log, logdb, spec):
    who = (spec["shard"], spec["rid"])
    n = SimpleNamespace(
        shard_id=spec["shard"], replica_id=spec["rid"], lane=spec["lane"],
        mirror=dict(spec.get("mirror", {})), logdb=logdb, mu=threading.Lock(),
        pending_proposals=Book(log, who, spec.get("futures", ())),
        pending_reads=Reads(log, who, spec.get("reads_pending", ())),
        pending_config_change=SimpleNamespace(
            done=lambda key, code, result=None:
            log.append(("cc-done", who, key, code))),
        sm=SM(log, who, spec.get("last_applied", 0)),
        rate_limiter=SimpleNamespace(enabled=lambda: False),
        notify_commit=spec.get("notify_commit", False),
        cfg=SimpleNamespace(snapshot_entries=0),
        applied_since_snapshot=0, _committed_cache=0, _staged_props=[],
        _local_ri_pending=dict(spec.get("local_ri", {})),
        _remote_ri_inflight=dict(spec.get("remote_ri", {})),
        _remote_reads=[], _leader_cache=spec.get("lead", (0, 0))[0],
        _leader_term_cache=spec.get("lead", (0, 0))[1],
        _last_leader=None, _transfer_awaiting=None,
        events=SimpleNamespace(leader_updated=lambda info: log.append(
            ("leader", who, info.leader_id, info.term))),
    )
    n.is_leader = lambda: n._leader_cache == n.replica_id
    n._rl_release = lambda key: None
    n.send_messages = lambda msgs: log.append(("send", who, tuple(msgs)))
    n.send_message = lambda m: log.append(("send", who, (m,)))
    n._on_config_change_applied = lambda e, r: log.append(
        ("cc-applied", who, e.index))
    return n


# -- a scene --------------------------------------------------------------------


class Scene:
    """A download and the lanes it speaks of."""

    def __init__(self):
        self.down = np.zeros((G, COLS.down_width), np.int32)
        self.v = kstate.column_views(COLS.down, self.down)
        self.lanes: dict[int, dict] = {}
        self.fates: dict[int, list] = {}        # lane -> [(entry, origin lane)]
        self.staged_ri: dict[int, pb.SystemCtx] = {}
        self.dead: set[int] = set()
        self.readmitted: set[int] = set()       # ctx holds the OLD node
        self.ring: dict[int, dict[int, int]] = {}   # lane -> index -> term
        self.cut: dict[int, list[int]] = {}     # mesh: lane -> cut peer rids
        self.snapshot = None

    def lane(self, g, shard=None, rid=1, peers=((1, VOTER), (2, VOTER),
                                                 (3, VOTER)), **spec):
        spec.update(lane=g, shard=shard or 100 + g, rid=rid, peers=peers)
        spec.setdefault("triple", (0, 0, 0))
        self.lanes[g] = spec
        # a quiet lane: what the kernel reports of a lane nothing happened to
        t, vt, cm = spec["triple"]
        lead, lead_term = spec.get("lead", (0, 0))
        self.put(g, term=t, vote=vt, commit=cm, leader=lead,
                 leader_term=lead_term, save_first=1, save_last=0,
                 apply_first=1, apply_last=0)
        return self

    def put(self, g, **cells):
        for f, val in cells.items():
            self.v[f][g] = val
        return self

    def flag(self, g, *classes):
        for c in classes:
            self.v["flags"][g, FLAG[c]] = 1
        return self

    def name_active(self):
        """Fill the ``active`` column as the round's program does
        (core/round.py ``row_activity``), a cell at a time: the lane's
        ``triple`` and ``lead`` are the state the step was given."""
        v = self.v
        for g, spec in self.lanes.items():
            output = (v["flags"][g].any() or v["ri_dropped"][g]
                      or v["needs_host"][g]
                      or v["save_last"][g] >= v["save_first"][g]
                      or v["apply_last"][g] >= v["apply_first"][g])
            triple = (v["term"][g], v["vote"][g],
                      v["commit"][g]) != tuple(spec["triple"])
            leader = (v["leader"][g],
                      v["leader_term"][g]) != tuple(spec.get("lead", (0, 0)))
            v["active"][g] = (
                bool(output) * kstate.ACTIVE_OUTPUT
                | triple * kstate.ACTIVE_TRIPLE
                | leader * kstate.ACTIVE_LEADER)
        return self


def E(index, term, key=0, cmd=b"", type=pb.EntryType.APPLICATION):
    return pb.Entry(term=term, index=index, key=key, cmd=cmd, type=type,
                    client_id=7 if key else 0, series_id=3 if key else 0)


# the cases: each fills a scene ------------------------------------------------


def responses_in_no_one_and_all_slots(s):
    s.lane(0).lane(1).lane(2)
    s.flag(1, "resp").put(1, r_type=[0, int(MT.REPLICATE_RESP), 0, 0],
                          r_to=[0, 2, 0, 0], r_term=[0, 5, 0, 0],
                          r_log_index=[0, 17, 0, 0], r_reject=[0, 1, 0, 0],
                          r_hint=[0, 9, 0, 0], r_hint_high=[0, 4, 0, 0])
    s.flag(2, "resp").put(
        2, r_type=[int(MT.HEARTBEAT_RESP), int(MT.REQUEST_VOTE_RESP),
                   int(MT.REPLICATE_RESP), int(MT.REQUEST_PREVOTE_RESP)],
        r_to=[3, 2, 3, 2], r_term=[6, 6, 7, 8], r_log_index=[1, 2, 3, 4],
        r_reject=[0, 1, 0, 1], r_hint=[11, 12, 13, 14],
        r_hint_high=[1, 0, 1, 0])


def a_response_whose_flag_is_clear_is_not_sent(s):
    s.lane(0).put(0, r_type=[int(MT.REPLICATE_RESP), 0, 0, 0],
                  r_to=[2, 0, 0, 0], r_term=[3, 0, 0, 0])
    s.lane(1).flag(1, "resp").put(1, r_type=[0, 0, int(MT.NOOP), 0],
                                  r_to=[0, 0, 3, 0])


def _leader(s, g, **kw):
    kw.setdefault("triple", (4, 1, 10))
    kw.setdefault("lead", (1, 4))
    return s.lane(g, **kw)


def replicates_of_zero_one_and_eight_entries(s):
    mirror = {i: E(i, 4, key=100 + i, cmd=b"v%d" % i) for i in range(1, 30)}
    _leader(s, 0, mirror=mirror).flag(0, "rep").put(
        0, s_rep=[0, 1, 1], s_prev_index=[0, 10, 12], s_prev_term=[0, 4, 4],
        s_commit=[0, 9, 10], s_n_ent=[0, 0, 1])
    s.v["s_ent_term"][0, 2, 0] = 4
    _leader(s, 1, mirror=mirror).flag(1, "rep").put(
        1, s_rep=[0, 1, 0], s_prev_index=[0, 20, 0], s_prev_term=[0, 4, 0],
        s_commit=[0, 19, 0], s_n_ent=[0, 8, 0])
    s.v["s_ent_term"][1, 1] = 4


def replicates_to_two_peers_at_the_same_prev(s):
    mirror = {i: E(i, 4, key=i, cmd=b"x") for i in range(1, 20)}
    _leader(s, 0, mirror=mirror).flag(0, "rep").put(
        0, s_rep=[0, 1, 1], s_prev_index=[0, 5, 5], s_prev_term=[0, 4, 4],
        s_commit=[0, 5, 5], s_n_ent=[0, 3, 3])
    s.v["s_ent_term"][0, 1:, :3] = 4


def replicates_to_two_peers_at_different_prev(s):
    mirror = {i: E(i, 4, key=i, cmd=b"x") for i in range(1, 20)}
    _leader(s, 0, mirror=mirror).flag(0, "rep").put(
        0, s_rep=[0, 1, 1], s_prev_index=[0, 5, 7], s_prev_term=[0, 4, 4],
        s_commit=[0, 5, 6], s_n_ent=[0, 3, 3])
    s.v["s_ent_term"][0, 1:, :3] = 4


def a_witness_peer_gets_metadata_entries(s):
    cc = E(7, 4, type=pb.EntryType.CONFIG_CHANGE, cmd=b"cc")
    mirror = {i: E(i, 4, key=i, cmd=b"x") for i in range(1, 12)}
    mirror[7] = cc
    _leader(s, 0, mirror=mirror,
            peers=((1, VOTER), (2, VOTER), (3, WITNESS))).flag(0, "rep").put(
        0, s_rep=[0, 1, 1], s_prev_index=[0, 5, 5], s_prev_term=[0, 4, 4],
        s_commit=[0, 5, 5], s_n_ent=[0, 3, 3])
    s.v["s_ent_term"][0, 1:, :3] = 4


def a_replicate_of_entries_the_mirror_lacks_or_holds_at_another_term(s):
    mirror = {6: E(6, 3, key=6, cmd=b"old"), 8: E(8, 4, key=8, cmd=b"x")}
    _leader(s, 0, mirror=mirror).flag(0, "rep").put(
        0, s_rep=[0, 1, 0], s_prev_index=[0, 5, 0], s_prev_term=[0, 3, 0],
        s_commit=[0, 5, 0], s_n_ent=[0, 3, 0])
    s.v["s_ent_term"][0, 1, :3] = 4


def heartbeats(s):
    _leader(s, 0).flag(0, "hb").put(
        0, s_hb=[0, 1, 1], s_hb_commit=[0, 9, 10], s_hb_low=[0, 21, 21],
        s_hb_high=[0, 1, 1])
    _leader(s, 1).flag(1, "hb").put(1, s_hb=[0, 0, 1], s_hb_commit=[0, 0, 3])
    s.lane(2).put(2, s_hb=[0, 1, 1])    # flag clear: none sent


def votes_and_prevotes(s):
    s.lane(0, triple=(5, 1, 3)).flag(0, "vote").put(
        0, s_vote=[0, 1, 1], s_vote_term=[0, 5, 5], s_vote_lindex=[0, 8, 8],
        s_vote_lterm=[0, 4, 4], s_vote_hint=[0, 2, 0])
    s.lane(1, rid=2, triple=(4, 0, 3)).flag(1, "vote").put(
        1, s_vote=[2, 0, 2], s_vote_term=[5, 0, 5], s_vote_lindex=[8, 0, 8],
        s_vote_lterm=[4, 0, 4])


def timeout_now(s):
    _leader(s, 0).flag(0, "timeout_now").put(0, s_timeout_now=[0, 0, 1])
    _leader(s, 1).put(1, s_timeout_now=[0, 1, 0])   # flag clear


def every_class_to_one_target_keeps_its_order(s):
    mirror = {i: E(i, 4, key=i, cmd=b"x") for i in range(1, 12)}
    _leader(s, 0, mirror=mirror).flag(
        0, "resp", "rep", "hb", "vote", "timeout_now").put(
        0, r_type=[int(MT.HEARTBEAT_RESP), 0, int(MT.REPLICATE_RESP), 0],
        r_to=[2, 0, 2, 0], r_term=[4, 0, 4, 0],
        s_rep=[0, 1, 0], s_prev_index=[0, 5, 0], s_prev_term=[0, 4, 0],
        s_n_ent=[0, 2, 0], s_hb=[0, 1, 1], s_hb_commit=[0, 5, 5],
        s_vote=[0, 2, 0], s_vote_term=[0, 5, 0], s_timeout_now=[0, 1, 0])
    s.v["s_ent_term"][0, 1, :2] = 4


def a_quiet_term_vote_commit_bump_is_persisted(s):
    s.lane(0, triple=(3, 2, 5)).put(0, term=4, vote=0)
    s.lane(1, triple=(3, 2, 5)).put(1, commit=6)
    s.lane(2, triple=(3, 2, 5)).flag(2, "hb")       # active, nothing to save


def save_windows_empty_partial_and_full(s):
    s.lane(0, triple=(4, 1, 5), mirror={6: E(6, 4, key=6, cmd=b"a")}).put(
        0, save_first=6, save_last=6, save_terms=[4, 0, 0, 0])
    mirror = {10: E(10, 3, key=10, cmd=b"stale"), 12: E(12, 4, key=12)}
    s.lane(1, rid=2, triple=(4, 1, 5), mirror=mirror).put(
        1, save_first=10, save_last=10 + S - 1, save_terms=[4] * S)
    s.lane(2, triple=(4, 1, 5)).flag(2, "hb")


def a_save_window_wider_than_the_download_reads_its_ring_row(s):
    s.lane(0, rid=2, triple=(4, 1, 5),
           mirror={i: E(i, 4, key=i, cmd=b"x") for i in range(20, 23)}).put(
        0, save_first=20, save_last=20 + S + 1, save_terms=[4] * S)
    s.ring[0] = {20 + j: 4 + (j >= S) for j in range(S + 2)}
    s.lane(1, triple=(4, 1, 5), mirror={6: E(6, 4)}).put(
        1, save_first=6, save_last=6, save_terms=[4, 0, 0, 0])


def apply_windows(s):
    mirror = {i: E(i, 4, key=200 + i, cmd=b"k=v") for i in range(1, 9)}
    s.lane(0, shard=7, rid=1, triple=(4, 1, 8), lead=(1, 4), mirror=mirror,
           futures={203, 204, 205, 206}, notify_commit=True,
           last_applied=2).put(0, apply_first=3, apply_last=6)
    # a follower of the same shard: its book holds no future
    s.lane(1, shard=7, rid=2, triple=(4, 1, 8), lead=(1, 4), mirror=mirror,
           last_applied=4).put(1, apply_first=3, apply_last=8)
    # an entry the mirror lacks is applied as an empty one at the lane's term
    s.lane(2, triple=(4, 1, 2), last_applied=0).put(
        2, apply_first=1, apply_last=2)


def a_config_change_applied(s):
    mirror = {3: E(3, 4, key=9, cmd=b"k=v"),
              4: E(4, 4, key=5, type=pb.EntryType.CONFIG_CHANGE, cmd=b"cc")}
    s.lane(0, triple=(4, 1, 4), mirror=mirror, futures={9},
           last_applied=2).put(0, apply_first=3, apply_last=4)


def proposal_fates(s):
    s.lane(0, shard=7, rid=1, triple=(4, 1, 8), lead=(1, 4),
           futures={31, 32, 33}).put(
        0, prop_accepted=[1, 0, 1, 0], prop_index=[9, 0, 10, 0],
        prop_term=[4, 0, 4, 0], save_first=9, save_last=10,
        save_terms=[4, 4, 0, 0])
    s.fates[0] = [(E(0, 0, key=31, cmd=b"a"), 0), (E(0, 0, key=32, cmd=b"b"), 0),
                  (E(0, 0, key=33, cmd=b"c"), 0)]
    # a refused config change, and a lane whose staged list is empty
    s.lane(1, triple=(4, 1, 8)).put(1, prop_accepted=[0, 0, 0, 0])
    s.fates[1] = [(E(0, 0, key=4, type=pb.EntryType.CONFIG_CHANGE), 1)]
    s.lane(2, triple=(4, 1, 8))
    s.fates[2] = []


def readindex_completions(s):
    s.lane(0, triple=(4, 1, 8), lead=(1, 4), last_applied=8,
           local_ri={5: pb.SystemCtx(low=5, high=1)}, reads_pending={5},
           remote_ri={6: 3}).flag(0, "rtr").put(
        0, rtr_valid=[1, 1], rtr_low=[5, 6], rtr_high=[1, 1],
        rtr_index=[8, 8])
    # confirmed at an index not applied yet: it waits
    s.lane(1, triple=(4, 1, 8), lead=(1, 4), last_applied=6,
           local_ri={9: pb.SystemCtx(low=9, high=1)},
           reads_pending={9}).flag(1, "rtr").put(
        1, rtr_valid=[0, 1], rtr_low=[0, 9], rtr_high=[0, 1],
        rtr_index=[0, 8])


def a_dropped_readindex(s):
    ctx = pb.SystemCtx(low=5, high=1)
    s.lane(0, triple=(4, 1, 8), lead=(1, 4), local_ri={5: ctx},
           reads_pending={5}).put(0, ri_dropped=1)
    s.staged_ri[0] = ctx
    fwd = pb.SystemCtx(low=6, high=1)
    s.lane(1, triple=(4, 1, 8), lead=(1, 4),
           remote_ri={6: 3}).put(1, ri_dropped=1)
    s.staged_ri[1] = fwd
    s.lane(2, triple=(4, 1, 8)).put(2, ri_dropped=1)    # nothing staged


def leader_edges(s):
    s.lane(0, rid=2, triple=(4, 1, 8), lead=(1, 4)).put(
        0, leader=3, leader_term=5, term=5, vote=3)
    s.lane(1, rid=2, triple=(4, 1, 8), lead=(1, 4)).flag(1, "hb")


def a_lane_that_needs_the_host_is_evicted(s):
    s.lane(0, triple=(4, 1, 8), mirror={9: E(9, 4, key=9)},
           last_applied=8).put(0, needs_host=1, commit=9, apply_first=9,
                               apply_last=9)
    s.lane(1, triple=(4, 1, 8)).flag(1, "hb")


def a_witness_snapshot_with_and_without_a_record(s):
    peers = ((1, VOTER), (2, VOTER), (3, WITNESS))
    s.snapshot = pb.Snapshot(filepath="/x", file_size=9, index=40, term=4,
                             shard_id=100)
    s.lane(0, triple=(4, 1, 50), lead=(1, 4), peers=peers).flag(
        0, "wit_snap", "hb").put(0, s_wit_snap=[0, 0, 1], s_hb=[0, 1, 1])


def a_witness_snapshot_without_a_record_goes_the_eviction_way(s):
    peers = ((1, VOTER), (2, VOTER), (3, WITNESS))
    s.lane(0, triple=(4, 1, 50), lead=(1, 4), peers=peers).flag(
        0, "wit_snap").put(0, s_wit_snap=[0, 0, 1])


def a_row_removed_in_flight(s):
    _leader(s, 0, futures={31}).flag(0, "hb").put(
        0, s_hb=[0, 1, 1], prop_accepted=[1, 0, 0, 0], prop_index=[11, 0, 0, 0],
        term=5)
    s.fates[0] = [(E(0, 0, key=31), 0)]
    s.dead.add(0)
    _leader(s, 1).flag(1, "hb").put(1, s_hb=[0, 1, 0])


def a_row_readmitted_in_flight(s):
    _leader(s, 0).flag(0, "hb").put(0, s_hb=[0, 1, 1], term=5,
                                    apply_first=1, apply_last=2)
    s.readmitted.add(0)
    _leader(s, 1).flag(1, "hb").put(1, s_hb=[0, 1, 0])


def _mesh_group(s, cut):
    """One mesh group's three rows (replica r+1 in row r * 4), all busy."""
    mirror = {i: E(i, 4, key=i, cmd=b"x") for i in range(1, 12)}
    for r in range(3):
        g = r * 4
        s.lane(g, shard=9, rid=r + 1, triple=(4, 1, 5), lead=(1, 4),
               mirror=mirror).flag(g, "resp", "hb", "rep", "vote").put(
            g, r_type=[int(MT.HEARTBEAT_RESP), int(MT.REPLICATE_RESP), 0, 0],
            r_to=[(r + 1) % 3 + 1, (r + 2) % 3 + 1, 0, 0], r_term=[4, 4, 0, 0],
            s_hb=[1, 1, 1], s_hb_commit=[5, 5, 5], s_rep=[1, 1, 1],
            s_prev_index=[5, 5, 5], s_prev_term=[4, 4, 4], s_n_ent=[2, 2, 2],
            s_vote=[1, 1, 1], s_vote_term=[5, 5, 5],
            save_first=6, save_last=7, save_terms=[4, 4, 0, 0])
        s.v["s_ent_term"][g, :, :2] = 4
    s.cut = cut


def a_mesh_with_every_link_resident_sends_nothing(s):
    _mesh_group(s, {})


def a_cut_mesh_link_rides_the_host(s):
    _mesh_group(s, {0: [2], 4: [1]})    # replica 1 <-> 2, both ends


def a_fully_cut_mesh_row(s):
    _mesh_group(s, {8: [1, 2, 3]})


def _random(seed):
    def random_scene(s):
        rng = np.random.default_rng(seed)
        K, P = KPARAMS.inbox_cap, KPARAMS.num_peers
        types = [int(t) for t in (MT.REPLICATE_RESP, MT.HEARTBEAT_RESP,
                                  MT.REQUEST_VOTE_RESP, MT.NOOP)]
        for g in range(G):
            if rng.random() < 0.2:
                continue
            rid = int(rng.integers(1, 4))
            mirror = {i: E(i, int(rng.integers(3, 5)), key=1000 * g + i,
                           cmd=b"p%d" % i)
                      for i in range(1, 40) if rng.random() < 0.8}
            futures = {e.key for e in mirror.values() if rng.random() < 0.5}
            s.lane(g, rid=rid, triple=(4, int(rng.integers(0, 4)), 10),
                   lead=(int(rng.integers(0, 4)), 4), mirror=mirror,
                   futures=futures, last_applied=int(rng.integers(8, 12)),
                   peers=((1, VOTER), (2, VOTER),
                          (3, WITNESS if rng.random() < 0.3 else VOTER)))
            s.put(g, r_type=rng.choice([0] + types, K), r_to=rng.integers(1, 4, K),
                  r_term=rng.integers(1, 9, K), r_log_index=rng.integers(0, 50, K),
                  r_reject=rng.integers(0, 2, K), r_hint=rng.integers(0, 99, K),
                  r_hint_high=rng.integers(0, 2, K),
                  s_rep=rng.integers(0, 2, P), s_prev_index=rng.integers(0, 20, P),
                  s_prev_term=rng.integers(1, 5, P), s_commit=rng.integers(0, 20, P),
                  s_n_ent=rng.integers(0, KPARAMS.msg_entries + 1, P),
                  s_hb=rng.integers(0, 2, P), s_hb_commit=rng.integers(0, 20, P),
                  s_hb_low=rng.integers(0, 99, P), s_hb_high=rng.integers(0, 2, P),
                  s_vote=rng.integers(0, 3, P), s_vote_term=rng.integers(1, 9, P),
                  s_vote_lindex=rng.integers(0, 50, P),
                  s_vote_lterm=rng.integers(1, 9, P),
                  s_vote_hint=rng.integers(0, 4, P),
                  s_timeout_now=rng.integers(0, 2, P),
                  term=rng.integers(4, 6), vote=rng.integers(0, 4),
                  commit=rng.integers(10, 14), leader=rng.integers(0, 4),
                  leader_term=rng.integers(4, 6),
                  save_terms=rng.integers(3, 6, S))
            s.v["s_ent_term"][g] = rng.integers(3, 6, (P, KPARAMS.msg_entries))
            first = int(rng.integers(10, 20))
            s.put(g, save_first=first,
                  save_last=first + int(rng.integers(-1, S)))
            first = int(rng.integers(9, 13))
            s.put(g, apply_first=first,
                  apply_last=first + int(rng.integers(-1, 6)))
            # the flags are the kernel's: a class's flag is the any() of
            # its column, and now and then a class is switched off whole
            v = s.v
            for cls, on in (("resp", v["r_type"][g].any()),
                            ("rep", v["s_rep"][g].any()),
                            ("hb", v["s_hb"][g].any()),
                            ("vote", v["s_vote"][g].any()),
                            ("timeout_now", v["s_timeout_now"][g].any())):
                if on and rng.random() < 0.85:
                    s.flag(g, cls)
            if rng.random() < 0.5:
                n = int(rng.integers(1, KPARAMS.proposal_cap + 1))
                s.fates[g] = [(E(0, 0, key=5000 + 10 * g + j, cmd=b"f"), g)
                              for j in range(n)]
                s.lanes[g]["futures"] |= {5000 + 10 * g + j for j in range(n)}
                s.put(g, prop_accepted=rng.integers(0, 2, KPARAMS.proposal_cap),
                      prop_index=40 + np.arange(KPARAMS.proposal_cap),
                      prop_term=[4] * KPARAMS.proposal_cap)
    random_scene.__name__ = f"random_scene_{seed}"
    return random_scene


CASES = [
    responses_in_no_one_and_all_slots,
    a_response_whose_flag_is_clear_is_not_sent,
    replicates_of_zero_one_and_eight_entries,
    replicates_to_two_peers_at_the_same_prev,
    replicates_to_two_peers_at_different_prev,
    a_witness_peer_gets_metadata_entries,
    a_replicate_of_entries_the_mirror_lacks_or_holds_at_another_term,
    heartbeats,
    votes_and_prevotes,
    timeout_now,
    every_class_to_one_target_keeps_its_order,
    a_quiet_term_vote_commit_bump_is_persisted,
    save_windows_empty_partial_and_full,
    a_save_window_wider_than_the_download_reads_its_ring_row,
    apply_windows,
    a_config_change_applied,
    proposal_fates,
    readindex_completions,
    a_dropped_readindex,
    leader_edges,
    a_lane_that_needs_the_host_is_evicted,
    a_witness_snapshot_with_and_without_a_record,
    a_witness_snapshot_without_a_record_goes_the_eviction_way,
    a_row_removed_in_flight,
    a_row_readmitted_in_flight,
    a_mesh_with_every_link_resident_sends_nothing,
    a_cut_mesh_link_rides_the_host,
    a_fully_cut_mesh_row,
    _random(1), _random(2), _random(3), _random(4),
]
MESH = {a_mesh_with_every_link_resident_sends_nothing,
        a_cut_mesh_link_rides_the_host, a_fully_cut_mesh_row}
#: the lanes a case takes through a rare class's per-lane handler
PER_LANE = {
    "a_save_window_wider_than_the_download_reads_its_ring_row": 1,
    "a_config_change_applied": 1,
    "proposal_fates": 1,
    "readindex_completions": 2,
    "a_dropped_readindex": 3,
    "a_lane_that_needs_the_host_is_evicted": 1,
    "a_witness_snapshot_with_and_without_a_record": 1,
    "a_witness_snapshot_without_a_record_goes_the_eviction_way": 1,
}


# -- the reference: a lane at a time, a cell at a time --------------------------


def cell(down, f, g, *at):
    """Field ``f`` of lane ``g`` at ``at``, read from the packed array by
    the column table alone."""
    c = COL[f]
    off = int(np.ravel_multi_index(at, c.shape)) if at else 0
    x = int(down[g, c.start + off])
    return bool(x) if c.dtype == "bool" else x


def _reference(scene, nodes, ctx_nodes, log, state, cut=None, mesh=False):
    """The retire pass as the per-lane engine made it: for every candidate
    lane in order its fates, its messages and its update; the replicates,
    the save, the other messages; then per lane reads, apply, leader edge,
    escalation.  ``state``: what the per-lane engine kept on the host
    of each lane (triple, lead, lead_term), as dicts by lane."""
    down = scene.down
    flag = lambda g, c: cell(down, "flags", g, FLAG[c])     # noqa: E731
    K, P = KPARAMS.inbox_cap, KPARAMS.num_peers

    def active(g):
        return (any(flag(g, c) for c in FLAG)
                or cell(down, "save_last", g) >= cell(down, "save_first", g)
                or cell(down, "apply_last", g) >= cell(down, "apply_first", g)
                or cell(down, "ri_dropped", g) or cell(down, "needs_host", g)
                or (cell(down, "term", g), cell(down, "vote", g),
                    cell(down, "commit", g)) != state["triple"][g]
                or cell(down, "leader", g) != state["lead"][g]
                or cell(down, "leader_term", g) != state["lead_term"][g])

    cand = [g for g in sorted(scene.lanes)
            if (active(g) or g in scene.fates) and g not in scene.dead
            and ctx_nodes[g] is nodes.get(g)]
    registered = {id(n) for n in nodes.values()}
    replicates, others, updates, fallback = [], [], [], []

    def linked(g, to):
        return not mesh or (1 <= to <= 3 and to in cut.get(g, ()))

    for g in cand:
        n = nodes[g]
        for slot, (entry, origin) in enumerate(scene.fates.get(g, ())):
            if cell(down, "prop_accepted", g, slot):
                index = cell(down, "prop_index", g, slot)
                n.mirror[index] = pb.Entry(
                    term=cell(down, "prop_term", g, slot), index=index,
                    type=entry.type, key=entry.key, client_id=entry.client_id,
                    series_id=entry.series_id,
                    responded_to=entry.responded_to, cmd=entry.cmd)
            elif entry.is_config_change():
                nodes[origin].pending_config_change.done(
                    entry.key, RequestResultCode.DROPPED)
            else:
                nodes[origin].pending_proposals.dropped(entry.key)
        term = cell(down, "term", g)
        if flag(g, "resp"):
            for k in range(K):
                mt = cell(down, "r_type", g, k)
                to = cell(down, "r_to", g, k)
                if mt and linked(g, to):
                    others.append((n, pb.Message(
                        type=MT(mt), to=to, from_=n.replica_id,
                        shard_id=n.shard_id, term=cell(down, "r_term", g, k),
                        log_index=cell(down, "r_log_index", g, k),
                        reject=cell(down, "r_reject", g, k),
                        hint=cell(down, "r_hint", g, k),
                        hint_high=cell(down, "r_hint_high", g, k))))
        peers = scene.lanes[g]["peers"]
        for p in range(P):
            to, kind = peers[p] if p < len(peers) else (0, 0)
            if to == 0 or to == n.replica_id:
                continue
            if flag(g, "wit_snap") and cell(down, "s_wit_snap", g, p):
                if mesh:
                    fallback.append(n)
                else:
                    ss = scene.snapshot
                    if ss is not None and ss.index >= 0:    # device floor: 0
                        others.append((n, pb.Message(
                            type=MT.INSTALL_SNAPSHOT, to=to,
                            from_=n.replica_id, shard_id=n.shard_id,
                            term=term, snapshot=pb.Snapshot(
                                index=ss.index, term=ss.term,
                                shard_id=ss.shard_id, witness=True))))
                    else:
                        fallback.append(n)
            if not linked(g, to):
                continue
            if flag(g, "rep") and cell(down, "s_rep", g, p):
                prev = cell(down, "s_prev_index", g, p)
                ents = []
                for j in range(cell(down, "s_n_ent", g, p)):
                    idx, t = prev + 1 + j, cell(down, "s_ent_term", g, p, j)
                    e = n.mirror.get(idx)
                    if e is None:
                        e = pb.Entry(index=idx, term=t)
                    elif e.term != t:
                        e = pb.Entry(
                            term=t, index=e.index, type=e.type, key=e.key,
                            client_id=e.client_id, series_id=e.series_id,
                            responded_to=e.responded_to, cmd=e.cmd)
                    if kind == WITNESS and not e.is_config_change():
                        e = pb.Entry(index=idx, term=t,
                                     type=pb.EntryType.METADATA)
                    ents.append(e)
                replicates.append((n, pb.Message(
                    type=MT.REPLICATE, to=to, from_=n.replica_id,
                    shard_id=n.shard_id, term=term, log_index=prev,
                    log_term=cell(down, "s_prev_term", g, p),
                    commit=cell(down, "s_commit", g, p),
                    entries=tuple(ents))))
            if flag(g, "hb") and cell(down, "s_hb", g, p):
                others.append((n, pb.Message(
                    type=MT.HEARTBEAT, to=to, from_=n.replica_id,
                    shard_id=n.shard_id, term=term,
                    commit=cell(down, "s_hb_commit", g, p),
                    hint=cell(down, "s_hb_low", g, p),
                    hint_high=cell(down, "s_hb_high", g, p))))
            sv = cell(down, "s_vote", g, p) if flag(g, "vote") else 0
            if sv:
                others.append((n, pb.Message(
                    type=MT.REQUEST_VOTE if sv == 1 else MT.REQUEST_PREVOTE,
                    to=to, from_=n.replica_id, shard_id=n.shard_id,
                    term=cell(down, "s_vote_term", g, p),
                    log_index=cell(down, "s_vote_lindex", g, p),
                    log_term=cell(down, "s_vote_lterm", g, p),
                    hint=cell(down, "s_vote_hint", g, p))))
            if flag(g, "timeout_now") and cell(down, "s_timeout_now", g, p):
                others.append((n, pb.Message(
                    type=MT.TIMEOUT_NOW, to=to, from_=n.replica_id,
                    shard_id=n.shard_id, term=term)))
        first, last = cell(down, "save_first", g), cell(down, "save_last", g)
        triple = (term, cell(down, "vote", g), cell(down, "commit", g))
        entries = []
        for idx in range(first, last + 1):
            t = (cell(down, "save_terms", g, idx - first)
                 if last - first < S else scene.ring[g][idx])
            e = n.mirror.get(idx)
            if e is None:
                e = n.mirror[idx] = pb.Entry(index=idx, term=t)
            elif e.term != t:
                e = n.mirror[idx] = pb.Entry(
                    term=t, index=e.index, type=e.type, key=e.key,
                    client_id=e.client_id, series_id=e.series_id,
                    responded_to=e.responded_to, cmd=e.cmd)
            entries.append(e)
        if entries or triple != state["triple"][g]:
            state["triple"][g] = triple
            updates.append(pb.Update(
                shard_id=n.shard_id, replica_id=n.replica_id,
                state=pb.State(term=triple[0], vote=triple[1],
                               commit=triple[2]),
                entries_to_save=tuple(entries)))

    def send_all(pairs):
        if mesh:
            for n, m in pairs:
                n.send_message(m)
            return
        for n, m in pairs:      # one batch a sending host (here: a node)
            n.send_messages([m])

    send_all(replicates)
    if updates:
        log.append(("save", tuple(updates)))
    send_all(others)

    for g in cand:
        n = nodes[g]
        if id(n) not in registered:
            continue
        n._committed_cache = cell(down, "commit", g)
        if flag(g, "rtr"):
            for j in range(KPARAMS.readindex_cap):
                if not cell(down, "rtr_valid", g, j):
                    continue
                low, high = (cell(down, "rtr_low", g, j),
                             cell(down, "rtr_high", g, j))
                index = cell(down, "rtr_index", g, j)
                if low in n._local_ri_pending:
                    n._local_ri_pending.pop(low)
                    n.pending_reads.add_ready(
                        pb.SystemCtx(low=low, high=high), index)
                elif low in n._remote_ri_inflight:
                    n.send_message(pb.Message(
                        type=MT.READ_INDEX_RESP,
                        to=n._remote_ri_inflight.pop(low),
                        from_=n.replica_id, shard_id=n.shard_id,
                        log_index=index, hint=low, hint_high=high))
        staged = scene.staged_ri.get(g)
        if cell(down, "ri_dropped", g) and staged is not None:
            if staged.low in n._local_ri_pending:
                n._local_ri_pending.pop(staged.low)
                n.pending_reads.dropped(staged)
            sender = n._remote_ri_inflight.pop(staged.low, None)
            if sender is not None and n.is_leader():
                n._remote_reads.insert(0, (sender, staged, 0))
        n.pending_reads.applied(n.sm.get_last_applied())
        first, last = (cell(down, "apply_first", g),
                       cell(down, "apply_last", g))
        if last >= first:
            entries = []
            for idx in range(first, last + 1):
                e = n.mirror.get(idx)
                if e is None:
                    e = n.mirror[idx] = pb.Entry(
                        index=idx, term=cell(down, "term", g))
                entries.append(e)
            if n.notify_commit:
                for e in entries:
                    if e.key:
                        n.pending_proposals.committed(e.key)
            cc = False
            for r in n.sm.handle(entries):
                entry = next(e for e in entries if e.index == r.index)
                if entry.is_config_change():
                    n._on_config_change_applied(entry, r)
                    cc = True
                elif r.key:
                    n.pending_proposals.applied(
                        r.key, r.client_id, r.series_id, r.result, r.rejected)
            if cc:
                log.append(("membership", (n.shard_id, n.replica_id)))
            n.applied_since_snapshot += len(entries)
            n.pending_reads.applied(n.sm.get_last_applied())
        lead = (cell(down, "leader", g), cell(down, "leader_term", g))
        if lead != (n._leader_cache, n._leader_term_cache):
            n._leader_cache, n._leader_term_cache = lead
            log.append(("leader", (n.shard_id, n.replica_id), *lead))
        state["lead"][g], state["lead_term"][g] = lead
        if cell(down, "needs_host", g):
            registered.discard(id(n))
            log.append(("evict", (n.shard_id, n.replica_id),
                        "kernel escalation"))
        elif n in fallback:
            registered.discard(id(n))
            log.append(("evict", (n.shard_id, n.replica_id),
                        "witness snapshot without record"))
    state["retired"] = set(cand)
    return len(cand)


# -- the comparison -------------------------------------------------------------


def _engine_for(case):
    if case in MESH:
        from dragonboat_tpu.engine.mesh_engine import MeshEngine

        eng = MeshEngine(KPARAMS, MeshSpec(
            name=f"retire-{next(_names)}", g_size=1, replicas=3, n_local=4))
        assert eng.capacity == G
        return eng
    return ke.KernelEngine(KPARAMS, capacity=G, send_message=None)


_names = itertools.count()


def _build(scene, log, engine=None):
    """-> (nodes by lane as the engine holds them, nodes by lane as the
    step's ctx holds them, the host arrays by lane)."""
    logdb = LogDB(log, scene.snapshot)
    nodes = {g: make_node(log, logdb, copy.deepcopy(spec))
             for g, spec in scene.lanes.items()}
    ctx_nodes = dict(nodes)
    for g in scene.readmitted:
        ctx_nodes[g] = make_node(log, logdb, copy.deepcopy(scene.lanes[g]))
    state = {"triple": {g: spec["triple"] for g, spec in scene.lanes.items()},
             "lead": {g: spec.get("lead", (0, 0))[0]
                      for g, spec in scene.lanes.items()},
             "lead_term": {g: spec.get("lead", (0, 0))[1]
                           for g, spec in scene.lanes.items()}}
    if engine is not None:
        mesh = hasattr(engine, "spec")
        for g, n in nodes.items():
            spec = scene.lanes[g]
            engine.nodes[g] = n
            engine.by_shard[(n.shard_id, n.replica_id) if mesh
                            else n.shard_id] = n
            for p, (rid, kind) in enumerate(spec["peers"]):
                engine._pid_np[g, p], engine._kind_np[g, p] = rid, kind
        if mesh:
            engine._dispatch.cut[:] = False
            for g, rids in scene.cut.items():
                for rid in rids:
                    engine._dispatch.cut[g, rid - 1] = True
        if scene.ring:
            import jax.numpy as jnp

            lt = np.zeros((G, KPARAMS.log_cap), np.int32)
            for g, terms in scene.ring.items():
                for idx, t in terms.items():
                    lt[g, idx & (KPARAMS.log_cap - 1)] = t
            engine._resident = engine._resident._replace(lt=jnp.asarray(lt))

        def evict(n, reason, carry=None):
            engine.by_shard = {k: v for k, v in engine.by_shard.items()
                               if v is not n}
            engine.nodes.pop(n.lane, None)
            engine._removed_nodes.append(n)
            log.append(("evict", (n.shard_id, n.replica_id), reason))

        engine._evict = evict
        engine.update_lane_membership = lambda n: log.append(
            ("membership", (n.shard_id, n.replica_id)))
    return nodes, ctx_nodes, state


def _sent(log):
    """-> ({(shard, target): [messages in order]} of the REPLICATEs,
    the same of everything else, with each message its place in the log
    relative to the save: -1 before it, +1 after it)."""
    saves = [i for i, ev in enumerate(log) if ev[0] == "save"]
    assert len(saves) <= 1, "one save a round"
    out = ({}, {})
    for i, ev in enumerate(log):
        if ev[0] != "send":
            continue
        for m in ev[2]:
            side = 0 if m.type == MT.REPLICATE else 1
            out[side].setdefault((m.shard_id, m.to), []).append(m)
            if saves and m.type != MT.READ_INDEX_RESP:
                assert (i < saves[0]) == (side == 0), (
                    f"{m.type.name} on the wrong side of the save")
    return out


def _rest(log):
    """The log without its sends, the order across lanes dropped: what
    happened to each (shard, replica), in order."""
    per: dict = {}
    for ev in log:
        if ev[0] == "save":
            for ud in ev[1]:
                per.setdefault((ud.shard_id, ud.replica_id), []).append(
                    ("saved", ud))
        elif ev[0] != "send":
            per.setdefault(ev[1], []).append((ev[0],) + tuple(ev[2:]))
    return per


def _counters():
    snap = telemetry.GLOBAL.snapshot()
    return {p: snap.get(f"engine_retire_lanes{{path={p}}}", 0)
            for p in ("columnar", "per_lane")}


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_the_pass_retires_a_download_as_the_per_cell_reference_does(case):
    scene = Scene()
    case(scene)
    scene.name_active()
    eng = _engine_for(case)
    try:
        log: list = []
        nodes, ctx_nodes, _state = _build(scene, log, eng)
        fates = {g: [(e, ctx_nodes[o]) for e, o in fl]
                 for g, fl in scene.fates.items()}
        for g, fl in fates.items():
            ctx_nodes[g]._staged_props = fl
        ctx = ke._StepCtx(
            nodes=ctx_nodes, fates=fates, staged_ri=dict(scene.staged_ri),
            staged_rows=set(scene.fates), out=scene.down.copy(),
            dead=set(scene.dead))
        before = _counters()
        with eng.mu, eng._round:
            eng._process_outputs(ctx)
        after = _counters()

        want_log: list = []
        ref_nodes, ref_ctx_nodes, ref_state = _build(scene, want_log)
        processed = _reference(scene, ref_nodes, ref_ctx_nodes, want_log,
                               ref_state, cut=scene.cut, mesh=case in MESH)

        got_rep, got_other = _sent(log)
        want_rep, want_other = _sent(want_log)
        assert got_rep == want_rep
        assert got_other == want_other
        assert _rest(log) == _rest(want_log)
        for g in scene.lanes:
            assert nodes[g].mirror == ref_nodes[g].mirror, f"mirror of {g}"
            assert nodes[g]._committed_cache == ref_nodes[g]._committed_cache
            assert (nodes[g]._leader_cache, nodes[g]._leader_term_cache) == (
                ref_nodes[g]._leader_cache, ref_nodes[g]._leader_term_cache)
            assert [(s_, c) for s_, c, _t in nodes[g]._remote_reads] == [
                (s_, c) for s_, c, _t in ref_nodes[g]._remote_reads]
            if g not in scene.dead:
                assert nodes[g]._staged_props == []
            if g not in scene.dead and g not in scene.readmitted:
                # what replaced the host's arrays: a triple that moved is
                # in the ``pb.State`` of the lane's Update, the leader in
                # the node's own caches
                saved = [ud.state for ev in log if ev[0] == "save"
                         for ud in ev[1] if (ud.shard_id, ud.replica_id) == (
                             nodes[g].shard_id, nodes[g].replica_id)]
                if ref_state["triple"][g] != tuple(scene.lanes[g]["triple"]):
                    assert [(st.term, st.vote, st.commit)
                            for st in saved] == [ref_state["triple"][g]]
                if g in ref_state["retired"]:
                    assert (nodes[g]._leader_cache,
                            nodes[g]._leader_term_cache) == (
                        ref_state["lead"][g], ref_state["lead_term"][g])
        assert eng._lanes_processed == processed
        assert after["per_lane"] - before["per_lane"] == PER_LANE.get(
            case.__name__, 0)
        assert (after["columnar"] - before["columnar"]
                + after["per_lane"] - before["per_lane"]) == processed
    finally:
        eng.close()


def test_a_leaders_entries_are_built_once_and_shared_by_the_peers_they_fit():
    scene = Scene()
    replicates_to_two_peers_at_the_same_prev(scene)
    scene.name_active()
    eng = _engine_for(replicates_to_two_peers_at_the_same_prev)
    try:
        log: list = []
        _nodes, ctx_nodes, _ = _build(scene, log, eng)
        ctx = ke._StepCtx(nodes=ctx_nodes, fates={}, staged_ri={},
                          staged_rows=set(), out=scene.down.copy())
        with eng.mu, eng._round:
            eng._process_outputs(ctx)
        reps = [m for ev in log if ev[0] == "send" for m in ev[2]]
        assert [m.to for m in reps] == [2, 3] and len(reps[0].entries) == 3
        assert reps[0].entries is reps[1].entries
        # built with every field given, no default made and thrown away
        assert reps[0].snapshot is reps[1].snapshot is ke._NO_SNAPSHOT
    finally:
        eng.close()

"""Kernel test harness: loopback router over the batched step kernel.

One kernel row = one replica of one raft group.  The router plays transport:
it gathers each step's outbound lanes (responses, replicate/heartbeat/vote
lanes) and scatters them into the inboxes of target rows — the in-process
analog of the reference's chan transport (plugin/chan), and the model for
device-to-device ICI routing later.
"""

from __future__ import annotations

import numpy as np

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.core import params as KP
from dragonboat_tpu.core.kernel import step
from dragonboat_tpu.core.kstate import ShardState, empty_inbox, empty_input, init_state

MT = pb.MessageType


class Msg:
    __slots__ = ("mtype", "frm", "to", "term", "log_term", "log_index",
                 "commit", "reject", "hint", "hint_high", "ents")

    def __init__(self, mtype, frm, to, term, log_term=0, log_index=0, commit=0,
                 reject=False, hint=0, hint_high=0, ents=()):
        self.mtype = int(mtype)
        self.frm = int(frm)
        self.to = int(to)
        self.term = int(term)
        self.log_term = int(log_term)
        self.log_index = int(log_index)
        self.commit = int(commit)
        self.reject = bool(reject)
        self.hint = int(hint)
        self.hint_high = int(hint_high)
        self.ents = ents  # list[(term, is_cc)]

    def __repr__(self):
        return (f"Msg({MT(self.mtype).name} {self.frm}->{self.to} t{self.term} "
                f"li{self.log_index} c{self.commit} rej{int(self.reject)} "
                f"ents{len(self.ents)})")


class KernelCluster:
    """num_groups raft groups × replicas-per-group rows in one kernel state."""

    def __init__(self, num_groups: int, replicas: int = 3,
                 kp: KP.KernelParams | None = None,
                 election: int = 10, heartbeat: int = 1,
                 check_quorum: bool = False, pre_vote: bool = False,
                 witnesses: frozenset[int] | set[int] = frozenset()):
        # one shared small geometry across tests → a single kernel compile
        self.kp = kp or KP.KernelParams(
            num_peers=max(3, replicas), log_cap=256, inbox_cap=4,
            msg_entries=4, proposal_cap=4, readindex_cap=4,
        )
        self.n = num_groups
        self.p = replicas
        self.witnesses = frozenset(witnesses)
        G = num_groups * replicas
        self.G = G
        rids = np.tile(np.arange(1, replicas + 1, dtype=np.int32), num_groups)
        peer_ids = np.zeros((G, self.kp.num_peers), np.int32)
        peer_ids[:, :replicas] = np.arange(1, replicas + 1, dtype=np.int32)
        peer_kinds = np.where(peer_ids != 0, KP.K_VOTER,
                              KP.K_ABSENT).astype(np.int32)
        for rid_w in self.witnesses:
            peer_kinds[:, rid_w - 1] = KP.K_WITNESS
        self.state: ShardState = init_state(
            self.kp, G, rids, peer_ids, peer_kinds=peer_kinds,
            election_timeout=election, heartbeat_timeout=heartbeat,
            check_quorum=check_quorum, pre_vote=pre_vote,
        )
        self.pending: list[list[Msg]] = [[] for _ in range(G)]  # inbox queues
        self.dropped_pairs: set[tuple[int, int]] = set()  # (row_from, row_to)
        self.isolated: set[int] = set()
        self.last_out = None

    def row(self, group: int, rid: int) -> int:
        return group * self.p + (rid - 1)

    def enqueue(self, row: int, msg: Msg) -> None:
        self.pending[row].append(msg)

    def _route(self, out) -> None:
        """Scatter one step's outbound lanes into pending queues."""
        o = {k: (np.asarray(v) if v is not None else None)
             for k, v in out._asdict().items()}
        K, P_, E = self.kp.inbox_cap, self.kp.num_peers, self.kp.msg_entries
        for g in range(self.G):
            group = g // self.p
            my_rid = g % self.p + 1
            if g in self.isolated:
                continue

            def deliver(to_rid, msg):
                if to_rid < 1 or to_rid > self.p:
                    return
                row = self.row(group, to_rid)
                if row in self.isolated or (g, row) in self.dropped_pairs:
                    return
                self.pending[row].append(msg)

            for k in range(K):
                t = int(o["r_type"][g, k])
                if t != 0:
                    deliver(int(o["r_to"][g, k]), Msg(
                        t, my_rid, int(o["r_to"][g, k]), int(o["r_term"][g, k]),
                        log_index=int(o["r_log_index"][g, k]),
                        reject=bool(o["r_reject"][g, k]),
                        hint=int(o["r_hint"][g, k]),
                        hint_high=int(o["r_hint_high"][g, k]),
                    ))
            for p_ in range(P_):
                to_rid = p_ + 1
                if bool(o["s_rep"][g, p_]):
                    n = int(o["s_n_ent"][g, p_])
                    ents = [
                        (int(o["s_ent_term"][g, p_, e]), bool(o["s_ent_cc"][g, p_, e]))
                        for e in range(n)
                    ]
                    deliver(to_rid, Msg(
                        MT.REPLICATE, my_rid, to_rid, int(o["term"][g]),
                        log_term=int(o["s_prev_term"][g, p_]),
                        log_index=int(o["s_prev_index"][g, p_]),
                        commit=int(o["s_commit"][g, p_]), ents=ents,
                    ))
                if bool(o["s_hb"][g, p_]) and (
                        int(o["s_hb_commit"][g, p_]) == KP.QUIESCE_WORD):
                    # the lane entered quiesce on its own clock and says so
                    deliver(to_rid, Msg(MT.QUIESCE, my_rid, to_rid, 0))
                elif bool(o["s_hb"][g, p_]):
                    deliver(to_rid, Msg(
                        MT.HEARTBEAT, my_rid, to_rid, int(o["term"][g]),
                        commit=int(o["s_hb_commit"][g, p_]),
                        hint=int(o["s_hb_low"][g, p_]),
                        hint_high=int(o["s_hb_high"][g, p_]),
                    ))
                v = int(o["s_vote"][g, p_])
                if v:
                    deliver(to_rid, Msg(
                        MT.REQUEST_VOTE if v == 1 else MT.REQUEST_PREVOTE,
                        my_rid, to_rid, int(o["s_vote_term"][g, p_]),
                        log_term=int(o["s_vote_lterm"][g, p_]),
                        log_index=int(o["s_vote_lindex"][g, p_]),
                        hint=int(o["s_vote_hint"][g, p_]),
                    ))
                if bool(o["s_timeout_now"][g, p_]):
                    deliver(to_rid, Msg(MT.TIMEOUT_NOW, my_rid, to_rid,
                                        int(o["term"][g])))

    def _build_inbox(self):
        K, E = self.kp.inbox_cap, self.kp.msg_entries
        box = {
            "mtype": np.zeros((self.G, K), np.int32),
            "from_": np.zeros((self.G, K), np.int32),
            "term": np.zeros((self.G, K), np.int32),
            "log_term": np.zeros((self.G, K), np.int32),
            "log_index": np.zeros((self.G, K), np.int32),
            "commit": np.zeros((self.G, K), np.int32),
            "reject": np.zeros((self.G, K), bool),
            "hint": np.zeros((self.G, K), np.int32),
            "hint_high": np.zeros((self.G, K), np.int32),
            "n_ent": np.zeros((self.G, K), np.int32),
            "ent_term": np.zeros((self.G, K, E), np.int32),
            "ent_cc": np.zeros((self.G, K, E), bool),
        }
        for g in range(self.G):
            q = self.pending[g][:K]
            self.pending[g] = self.pending[g][K:]
            for k, m in enumerate(q):
                box["mtype"][g, k] = m.mtype
                box["from_"][g, k] = m.frm
                box["term"][g, k] = m.term
                box["log_term"][g, k] = m.log_term
                box["log_index"][g, k] = m.log_index
                box["commit"][g, k] = m.commit
                box["reject"][g, k] = m.reject
                box["hint"][g, k] = m.hint
                box["hint_high"][g, k] = m.hint_high
                ents = m.ents[:E]
                box["n_ent"][g, k] = len(ents)
                for e, (t, cc) in enumerate(ents):
                    box["ent_term"][g, k, e] = t
                    box["ent_cc"][g, k, e] = cc
        from dragonboat_tpu.core.kstate import Inbox

        return Inbox(**{k: np.asarray(v) for k, v in box.items()})

    def step(self, tick=False, proposals=None, reads=None, transfers=None,
             applied_sync=True):
        """One kernel step. tick: every row's, or [G] bool (rows whose
        engines tick at their own rates). proposals: {row: n_entries or [(is_cc)...]},
        reads: {row: (low, high)}, transfers: {row: target_rid}."""
        inp = empty_input(self.kp, self.G)
        d = {k: (np.asarray(v).copy() if v is not None else None)
             for k, v in inp._asdict().items()}
        d["tick"][:] = tick         # one bool, or one a row
        if proposals:
            for row, spec in proposals.items():
                if isinstance(spec, int):
                    spec = [False] * spec
                for b, is_cc in enumerate(spec[: self.kp.proposal_cap]):
                    d["prop_valid"][row, b] = True
                    d["prop_cc"][row, b] = is_cc
        if reads:
            for row, (lo, hi) in reads.items():
                d["ri_valid"][row] = True
                d["ri_low"][row] = lo
                d["ri_high"][row] = hi
        if transfers:
            for row, target in transfers.items():
                d["transfer_to"][row] = target
        if applied_sync:
            d["applied"] = np.asarray(self.state.processed)
        from dragonboat_tpu.core.kstate import StepInput

        box = self._build_inbox()
        self.state, out = self._kernel_step(
            box, StepInput(**{k: (np.asarray(v) if v is not None else None)
                              for k, v in d.items()}))
        self.last_out = out
        self._route(out)
        return out

    def _kernel_step(self, box, inp):
        """-> (state, out) of this cluster's rows after one kernel step."""
        return step(self.kp, self.state, box, inp)

    def run_until_leader(self, group: int = 0, max_steps: int = 200):
        for i in range(max_steps):
            self.step(tick=True)
            if self.leader_row(group) is not None:
                # drain in-flight messages without ticking
                for _ in range(6):
                    self.step()
                return i
        raise AssertionError("no leader elected")

    def leader_row(self, group: int):
        role = np.asarray(self.state.role)
        for r in range(group * self.p, (group + 1) * self.p):
            if role[r] == KP.LEADER:
                return r
        return None

    def drain(self, steps: int = 10):
        for _ in range(steps):
            self.step()

    def field(self, name: str):
        return np.asarray(getattr(self.state, name))


class TallCluster(KernelCluster):
    """A KernelCluster whose rows are the LAST rows of a state ``height``
    rows tall (4,096: an engine as wide as ``fleet-4k`` states it): every
    step runs the kernel over the whole height, the rows below are live
    lanes of their own (fresh three-replica members that tick, campaign and
    are never answered), and everything the harness reads (``state``, the
    step's output) is the cluster's own rows, sliced out.  Seeds are the
    short cluster's, so a schedule elects whom it elects there."""

    def __init__(self, *args, height: int = 4096, **kw) -> None:
        super().__init__(*args, **kw)
        self.height, self.base = height, height - self.G
        assert self.base >= 0
        below = init_state(
            self.kp, self.base,
            np.arange(self.base, dtype=np.int32) % 3 + 1,
            np.asarray([1, 2, 3] + [0] * (self.kp.num_peers - 3), np.int32))
        self.tall = self._stack(below, self.state)
        self._no_mail = empty_inbox(self.kp, self.base)

    @staticmethod
    def _stack(below, top):
        import jax

        return jax.tree.map(
            lambda b, t: np.concatenate([np.asarray(b), np.asarray(t)]),
            below, top)

    def _kernel_step(self, box, inp):
        import jax

        ticking = bool(np.asarray(inp.tick).any())
        fill_inp = empty_input(self.kp, self.base)._replace(
            tick=np.full((self.base,), ticking),
            applied=np.asarray(self.tall.processed)[:self.base])
        self.tall, out = step(
            self.kp, self.tall,
            self._stack(self._no_mail, box),
            self._stack(fill_inp, inp))
        mine = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: x[self.base:], tree)
        return mine(self.tall), mine(out)

"""The rows a round retires are named by the round's program
(``core/round.py`` ``row_activity``: the download's ``active`` column), and
they are the rows the host used to find for itself.

The oracle below is the parent's host expression, copied: a gather of the
occupied rows' mask columns from the download, compared with its OWN
``seen`` array (what the host last saw of a lane: the persisted triple,
the cached leader and term), kept as the parent kept it.  It rides every
``_process_outputs`` of every engine of a small cluster and holds the
engine to it round for round: the same rows handed to ``_Retiring``, the
same Updates handed to ``save_raft_state`` (who, the ``pb.State``, the
entries), and never a row that holds no replica (on a serial engine such
a row is never even named: nothing reaches it).  One story goes through
it on the serial engines and on the mesh engine, at depth 0 and depth 1:
elections, writes, a term bump with nothing to send, a leader transfer, a
replica injected mid-run, a lane cleared and its row used again, a
membership change, a replica that joins with an empty peer book, a replica
that starts again at its persisted term, a ReadIndex the leader's full
book drops, a group asleep and awake again.

A mismatch is recorded, not raised (the pass runs on the engine's own
thread, and an exception there is fatal to the host); each chapter ends by
asserting that none was.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from dragonboat_tpu import raftpb as pb, telemetry
from dragonboat_tpu.config import (
    Config, ExpertConfig, MeshSpec, NodeHostConfig,
)
from dragonboat_tpu.core.kstate import (
    ACTIVE_LEADER, ACTIVE_OUTPUT, ACTIVE_TRIPLE, FLAG_CLASSES,
)
from dragonboat_tpu.engine import kernel_engine as ke
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.request import RequestError

from test_nodehost import KVStateMachine, wait_leader

MT = pb.MessageType
ELECTION = 10
# the parent's mask columns after the flags, in its order
MASK_FIELDS = ("ri_dropped", "needs_host", "save_first", "save_last",
               "apply_first", "apply_last", "term", "vote", "commit",
               "leader", "leader_term")
M_BITS = len(FLAG_CLASSES) + 2
M_SAVE_FIRST, M_SAVE_LAST, M_APPLY_FIRST, M_APPLY_LAST, M_SEEN = range(
    M_BITS, M_BITS + 5)


class Oracle:
    """The parent's activity mask and ``seen`` array beside one engine."""

    def __init__(self, eng):
        self.eng = eng
        at = eng._at
        self.active_at = at["active"]
        self.mask_cols = np.array(
            [*range(len(FLAG_CLASSES)), *(at[f] for f in MASK_FIELDS)],
            np.intp)
        self.seen_cols = self.mask_cols[M_SEEN:]
        self.seen = np.zeros((eng.capacity, 5), np.int64)
        self.seen[:, :3] = -1
        self.occ = np.zeros((eng.capacity,), bool)
        self.mismatches: list[str] = []
        self.rounds = 0
        self.rows = 0
        self.updates = 0
        self.in_flight = 0          # rows whose lane changed hands in flight
        self.bits: dict[int, int] = {}      # active cell -> rows that read it
        self.dropped_reads = 0
        self.vote_alone = 0         # rows whose vote moved and term did not
        self.strays = 0             # named rows that held no replica
        self._lanes = self._updates = None
        for name in ("_inject", "_clear_lane", "_build_updates",
                     "_process_outputs"):
            setattr(eng, name, getattr(self, name)(getattr(eng, name)))

    # -- what the parent wrote its arrays on ---------------------------------

    def _inject(self, inner):
        def inject(lane, node, init):
            inner(lane, node, init)
            self.seen[lane] = (init.term, init.vote, init.committed, 0, 0)
            self.occ[lane] = True
        return inject

    def _clear_lane(self, inner):
        def clear(lane):
            inner(lane)
            self.seen[lane, :3] = -1
            self.occ[lane] = False
        return clear

    # -- what the engine did -------------------------------------------------

    def _build_updates(self, inner):
        def build(r):
            self._lanes = list(r.lanes)
            self._updates = inner(r)
            return self._updates
        return build

    def _process_outputs(self, inner):
        def process(ctx):
            host = np.array(ctx.out)
            try:
                want = self.expect(ctx, host)
            except Exception as e:                      # noqa: BLE001
                self.mismatches.append(f"the oracle itself: {e!r}")
                want = None
            self._lanes = self._updates = None
            inner(ctx)
            if want is not None:
                self.compare(ctx, host, *want)
        return process

    def expect(self, ctx, host):
        """The parent's expression -> (its candidate rows before the
        filters, the rows it retires, the Updates it saves)."""
        eng = self.eng
        live = np.nonzero(self.occ)[0]
        m = host[live[:, None], self.mask_cols]
        active = (
            m[:, :M_BITS].any(1)
            | (m[:, M_SAVE_LAST] >= m[:, M_SAVE_FIRST])
            | (m[:, M_APPLY_LAST] >= m[:, M_APPLY_FIRST])
            | (m[:, M_SEEN:] != self.seen[live]).any(1))
        raw = set(live[active].tolist())
        cand = (raw | set(ctx.staged_rows)) - set(ctx.dead)
        lanes = [g for g in sorted(cand) if g in ctx.nodes
                 and eng.nodes.get(g) is ctx.nodes[g]]
        at = eng._at
        updates = []
        for g in lanes:
            row = host[g]
            triple = [int(row[at[f]]) for f in ("term", "vote", "commit")]
            lo, hi = int(row[at["save_first"]]), int(row[at["save_last"]])
            if triple != self.seen[g, :3].tolist() or hi >= lo:
                n = ctx.nodes[g]
                updates.append((n.shard_id, n.replica_id, tuple(triple),
                                tuple(range(lo, hi + 1))))
        return raw, lanes, updates

    def compare(self, ctx, host, raw, lanes, updates):
        eng, say = self.eng, self.mismatches.append
        self.rounds += 1
        cells = host[:, self.active_at]
        named = set(np.nonzero(cells)[0].tolist())
        # a named row that held no replica when the step left: something
        # reached it all the same (on the mesh a peer's message, routed on
        # the device into a row its replica has left; on a serial engine
        # nothing can).  The parent never looked at such a row, and the
        # engine must not retire it (``lanes`` below holds it to that)
        strays = named - set(ctx.nodes)
        self.strays += len(strays)
        # ...and the rows the host adds for itself: a replica placed at a
        # term > 0, which the parent named by its leader cells (0, 0)
        named = (named - strays) | set(ctx.injected)
        # before the filters the two differ only where a lane changed
        # hands while its step was in flight (depth 1): the parent looked
        # at the lane's occupancy when it retired, the program when it ran
        moved = set(ctx.dead) | {
            g for g in raw ^ named
            if eng.nodes.get(g) is not ctx.nodes.get(g)}
        self.in_flight += len((raw ^ named) & moved)
        apart = sorted((raw ^ named) - moved)
        if apart:
            say(f"round {self.rounds}: the parent names {sorted(raw)}, the "
                f"column {sorted(named)}; (row, cell, seen, download): "
                f"{[(g, int(cells[g]), self.seen[g].tolist(),
                     host[g, self.seen_cols].tolist()) for g in apart]}")
        if self._lanes != lanes:
            say(f"round {self.rounds}: retired {self._lanes}, the parent "
                f"retires {lanes}")
        got = [(n.shard_id, n.replica_id,
                (ud.state.term, ud.state.vote, ud.state.commit),
                tuple(e.index for e in ud.entries_to_save))
               for n, ud in self._updates or ()]
        if got != updates:
            say(f"round {self.rounds}: saved {got}, the parent saves "
                f"{updates}")
        self.rows += len(lanes)
        self.updates += len(updates)
        for g in lanes:
            cell = int(cells[g])
            self.bits[cell] = self.bits.get(cell, 0) + 1
            self.dropped_reads += int(host[g, eng._at["ri_dropped"]] != 0)
            was, now = self.seen[g], host[g, self.seen_cols]
            self.vote_alone += int(was[0] == now[0] and was[1] != now[1])
        # ...and the parent's writes to its arrays: the triple of a row
        # it retired where it moved, the leader cells where the edge fired
        # (both leave the row equal to the download)
        if lanes:
            self.seen[lanes] = host[lanes][:, self.seen_cols]


# -- the clusters -------------------------------------------------------------


class Story:
    def __init__(self, kind: str, depth: int):
        self.kind, self.depth = kind, depth
        self.prefix = f"rn{kind[0]}{depth}x{time.monotonic_ns() % 10**9}"
        self.addrs = {i: f"{self.prefix}-{i}" for i in (1, 2, 3)}
        self.hosts: dict[int, NodeHost] = {}
        spec = (MeshSpec(name=self.prefix, g_size=2, replicas=3, n_local=4)
                if kind == "mesh" else None)
        for rid, addr in self.addrs.items():
            # (the serial hosts' clocks differ, as three engines' do: the
            # first lane to cross the quiesce threshold tells the others)
            self.hosts[rid] = NodeHost(NodeHostConfig(
                raft_address=addr,
                rtt_millisecond=5 if spec else 4 + rid,
                expert=ExpertConfig(
                    mesh=spec, kernel_log_cap=256, kernel_capacity=8,
                    kernel_apply_batch=16, kernel_compaction_overhead=16,
                    kernel_readindex_cap=1, kernel_pipeline_depth=depth)))
        # one oracle an engine, attached as the engine is made (a host
        # makes its engine for its first replica)
        self.oracles: list[Oracle] = []

    def start(self, shard: int, quiesce=False, hosts=(1, 2, 3), founders=None,
              join=False):
        members = {} if join else {r: self.addrs[r]
                                   for r in founders or self.addrs}
        for rid in hosts:
            self.hosts[rid].start_replica(
                members, join, KVStateMachine, Config(
                    shard_id=shard, replica_id=rid, election_rtt=ELECTION,
                    heartbeat_rtt=2, quiesce=quiesce,
                    device_resident=self.kind == "serial",
                    mesh_resident=self.kind == "mesh"))

    def node(self, rid: int, shard: int):
        return self.hosts[rid].nodes[shard]

    def engine_of(self, rid: int):
        return self.hosts[rid].mesh_engine if self.kind == "mesh" \
            else self.hosts[rid].kernel_engine

    def leader(self, shard: int) -> int:
        return wait_leader(
            {r: nh for r, nh in self.hosts.items() if shard in nh.nodes},
            shard_id=shard, timeout=60)

    def lead_from(self, shard: int, target: int, seconds=30.0):
        """Move the shard's leader to ``target`` (asked again: a transfer
        is refused while one is outstanding, dropped after an election)."""
        deadline = time.time() + seconds
        while self.leader(shard) != target:
            try:
                self.hosts[self.leader(shard)].request_leader_transfer(
                    shard, target)
            except RequestError:
                pass
            time.sleep(0.3)
            assert time.time() < deadline, "the leader did not move"

    def write(self, shard: int, cmd: bytes, seconds=30.0):
        deadline = time.time() + seconds
        while True:
            nh = self.hosts[self.leader(shard)]
            try:
                return nh.sync_propose(nh.get_noop_session(shard), cmd,
                                       timeout_s=5)
            except RequestError:
                assert time.time() < deadline, f"{cmd!r} never went through"
                time.sleep(0.05)

    def settle(self, seconds=1.0):
        """Let the engines retire what is on its way."""
        time.sleep(seconds)

    def clean(self):
        said = [m for o in self.oracles for m in o.mismatches]
        assert not said, "\n".join(said[:10])

    def total(self, what: str) -> int:
        return sum(getattr(o, what) for o in self.oracles)

    def bits(self) -> dict:
        out: dict = {}
        for o in self.oracles:
            for cell, n in o.bits.items():
                out[cell] = out.get(cell, 0) + n
        return out

    def asleep(self, shard: int) -> list:
        out = []
        for rid in self.hosts:
            eng, node = self.engine_of(rid), self.node(rid, shard)
            with eng.mu:
                out.append(bool(np.asarray(eng.state.quiesced)[node.lane]))
        return out

    def close(self):
        for nh in self.hosts.values():
            nh.close()


def wait_for(cond, seconds, what):
    deadline = time.time() + seconds
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.05)
    assert cond(), what


@pytest.fixture(scope="module", params=[
    pytest.param((kind, depth), id=f"{kind}-depth{depth}")
    for kind in ("serial", "mesh") for depth in (0, 1)])
def story(request):
    kind, depth = request.param
    if kind == "mesh":
        import jax

        if len(jax.devices()) < 6:
            pytest.skip("the mesh engine needs 6 devices")
    s = Story(kind, depth)
    made = ke.KernelEngine.__init__

    def watched(eng, *a, **kw):
        made(eng, *a, **kw)
        s.oracles.append(Oracle(eng))

    ke.KernelEngine.__init__ = watched
    try:
        s.start(1)
        s.start(2, quiesce=True)
        assert len(s.oracles) == (1 if kind == "mesh" else 3)
        yield s
    finally:
        ke.KernelEngine.__init__ = made
        s.close()


# -- the story, a chapter a test ----------------------------------------------


def test_elections_and_writes(story):
    s = story
    for shard in (1, 2):
        s.leader(shard)
    for i in range(20):
        s.write(1, f"a{i}={i}".encode())
    s.write(2, b"b=1")
    s.settle()
    s.clean()
    assert s.total("rounds") > 20 and s.total("updates") > 20
    bits = s.bits()
    # a row with entries to save or apply, a term that moved with a vote
    # to send, a leader that moved
    assert any(c & ACTIVE_OUTPUT for c in bits)
    assert any(c & ACTIVE_TRIPLE for c in bits)
    assert any(c & ACTIVE_LEADER for c in bits)


def test_a_term_bump_with_nothing_to_send_and_a_transfer(story):
    s = story
    lead = s.leader(1)
    follower, other = (r for r in s.hosts if r != lead)
    node, eng = s.node(follower, 1), s.engine_of(follower)
    quiet = ACTIVE_TRIPLE | ACTIVE_LEADER       # and no ACTIVE_OUTPUT
    before = s.bits().get(quiet, 0), s.total("vote_alone")

    def deliver(**m):
        s.hosts[follower]._handle_message_batch(pb.MessageBatch(
            requests=(pb.Message(to=follower, from_=other, shard_id=1,
                                 **m),),
            source_address=s.addrs[other]))
        for _ in range(3):              # (depth 1 retires a round late)
            eng.step_all()

    if s.kind == "mesh":
        # (the hub carries a mesh link only while the link is cut)
        eng.set_link_hub_served(node, other, True)
    # the follower's engine is held, and its rounds driven from here, so
    # that the two messages meet the lane a round apart
    with eng.mu:
        term = int(np.asarray(eng.state.term)[node.lane]) + 3
        # a response from a later term: the lane steps down into that
        # term and answers nothing
        deliver(type=MT.HEARTBEAT_RESP, term=term)
        assert s.bits().get(quiet, 0) > before[0], (
            "no row moved its term without a word")
        # a candidate of that term asks: the vote moves, the term stays
        deliver(type=MT.REQUEST_VOTE, term=term, log_term=term,
                log_index=1 << 20)
        assert s.total("vote_alone") > before[1], (
            "no vote was granted within a term")
    if s.kind == "mesh":
        eng.set_link_hub_served(node, other, False)
    # the group elects again in the later term, and serves
    s.write(1, b"after-bump=1")
    lead = s.leader(1)
    s.lead_from(1, next(r for r in s.hosts if r != lead))
    s.write(1, b"after-transfer=1")
    s.settle()
    s.clean()


def test_a_replica_injected_a_row_used_again_a_membership_changed(story):
    s = story
    s.start(3)                          # injected beside running groups
    s.write(3, b"c=1")
    lanes = {rid: s.node(rid, 3).lane for rid in s.hosts}
    for nh in s.hosts.values():
        nh.stop_replica(3)              # cleared...
    s.write(1, b"while-empty=1")
    s.start(4)                          # ...and the rows used again
    assert {rid: s.node(rid, 4).lane for rid in s.hosts} == lanes
    s.write(4, b"d=1")
    # a member leaves: every remaining row's peer book is written again
    # (``update_lane_membership``)
    # (the last replica, not the leader: a mesh group whose MIDDLE
    # replica leaves and that is written to at once elects without end,
    # on the parent too: PERF.md section 7)
    gone = 3
    if s.leader(4) == gone:
        s.lead_from(4, 1)
    deadline = time.time() + 30
    while True:
        try:
            s.hosts[s.leader(4)].sync_request_delete_replica(
                4, gone, timeout_s=5)
            break
        except RequestError:
            assert time.time() < deadline, "the member never left"
            time.sleep(0.1)
    s.write(4, b"d=2")
    s.write(1, b"after-membership=1")
    s.settle()
    s.clean()


def test_a_replica_that_joins_with_an_empty_book(story):
    s = story
    # two founders, and a third replica that joins: its row is injected
    # with NO peer (``start_replica({}, join=True)``) and learns its book
    # from the config change the leader replicates to it, so that row has
    # to be retired (saved, answered, applied) while its book is empty
    s.start(5, hosts=(1, 2), founders=(1, 2))
    s.write(5, b"e=1")
    deadline = time.time() + 30
    while True:
        try:
            s.hosts[s.leader(5)].sync_request_add_replica(
                5, 3, s.addrs[3], timeout_s=5)
            break
        except RequestError:
            assert time.time() < deadline, "the member was never added"
            time.sleep(0.1)
    rows = s.total("rows")
    s.start(5, hosts=(3,), join=True)
    s.write(5, b"e=2")
    wait_for(lambda: s.hosts[3].stale_read(5, "e") == "2", 30,
             "the joiner never applied what the leader sent it")
    m = s.node(3, 5).sm.get_membership()
    assert set(m.addresses) == {1, 2, 3}
    assert s.total("rows") > rows
    s.settle()
    s.clean()


def test_a_replica_that_starts_again_at_its_persisted_term(story):
    s = story
    # a follower of shard 1 stops and starts again: its row is placed at
    # the term its log holds (> 0 since the first chapter), and the parent
    # named it in its first pass by its leader cells (0, 0) alone.  (Here
    # that pass has a log to apply as well, so the column names the row
    # too; ``tests/test_round_budget.py`` places a row with nothing else
    # to do.)
    lead = s.leader(1)
    again = next(r for r in s.hosts if r != lead)
    s.hosts[again].stop_replica(1)
    s.write(1, b"while-away=1")
    s.start(1, hosts=(again,))
    node = s.node(again, 1)
    wait_for(lambda: node._leader_term_cache > 0, 30,
             "the replica that started again never heard of its term")
    s.write(1, b"back=1")
    wait_for(lambda: s.hosts[again].stale_read(1, "back") == "1", 30,
             "the replica that started again never caught up")
    s.settle()
    s.clean()


def test_a_dropped_read_and_a_group_asleep_and_awake(story):
    s = story
    # the leader's ReadIndex book holds ONE context: readers that come a
    # round apart find it full
    nh = s.hosts[s.leader(1)]
    stop = threading.Event()
    served = []

    def reader():
        while not stop.is_set():
            try:
                served.append(nh.sync_read(1, "a1", timeout_s=2))
            except RequestError:
                pass

    threads = [threading.Thread(target=reader, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        wait_for(lambda: s.total("dropped_reads") > 0 and served, 30,
                 "no read was turned away by a full book")
    finally:
        stop.set()
        for t in threads:
            t.join(10)
    assert set(served) == {"1"}
    # shard 2 has been idle since the first chapter: all three replicas
    # sleep, and a write wakes them
    wait_for(lambda: all(s.asleep(2)), 60, "shard 2 never went to sleep")
    rows = s.total("rows")
    s.settle(1.0)
    s.write(2, b"b=2")
    assert not any(s.asleep(2)[i] for i in (s.leader(2) - 1,))
    wait_for(lambda: all(nh.stale_read(2, "b") == "2"
                         for nh in s.hosts.values()), 15,
             "the write did not reach every replica")
    assert s.total("rows") > rows
    s.settle()
    s.clean()


def test_every_round_was_held_to_the_parent(story):
    s = story
    s.clean()
    assert s.total("rounds") > 200
    # the counter: of the rows retired, those the column named
    snap = telemetry.GLOBAL.snapshot()
    device = snap.get("engine_retire_named{by=device}", 0)
    host = snap.get("engine_retire_named{by=host}", 0)
    assert device > 0 and device >= 9 * host
    if s.kind == "serial":
        assert s.total("strays") == 0, (
            "a row that holds no replica was named, and nothing reaches "
            "such a row on a serial engine")


"""A Raft group enters quiesce together, and leaves it together.

Two forms of one rule: ``quiesce.py``'s ``QuiesceState`` driven by ``node.py``
(the host path, the plain reference: upstream's ``quiesce.go``) and the
kernel's quiesce block (``core/kernel.py`` steps 0b and 5b, the path a
device-resident replica takes).  What a group SHOWS has to agree: all three
replicas asleep within ``election_rtt`` ticks of the first, no term and no
leader moved across the entry, a proposal / a read / a transfer wakes the
group and it commits, a replica that just left quiesce is not pulled back
in by a peer's stale word.  A third form of the same block since PR 39: the
mesh engine (``engine/mesh_engine.py``), where the three replicas are rows of
ONE engine on three devices, the word rides the step's collectives and all
three rows tick on one clock.

Part A drives the kernel alone on seeded schedules whose three rows tick at
rates up to 30% apart (a tick is a round of the row's own engine, and a
group's three engines do not step alike).  On the parent's kernel, where a
lane entered alone on its own clock, the no-election case fails: a follower
at 70% of its leader's rate is 30 ticks short when the leader goes silent,
and campaigns 10-19 ticks later.  Part B runs the same story through three
NodeHosts whose ``rtt_millisecond`` differ by 30%, once a path: the host
path, the kernel path (an engine a host) and the mesh path (one engine for
the three hosts, on forced host devices).
"""

import random
import time
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.config import (
    Config, ExpertConfig, MeshSpec, NodeHostConfig,
)
from dragonboat_tpu.core import params as KP
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.request import RequestError

from kernel_harness import KernelCluster, Msg
from test_nodehost import KVStateMachine, wait_leader

MT = pb.MessageType
ELECTION = 10                   # election_rtt, in ticks
THRESHOLD = ELECTION * 10       # quiesce.py: idle ticks before entry


# ---------------------------------------------------------------------------
# Part A: the kernel, rows ticking at their own rates
# ---------------------------------------------------------------------------


class SkewedGroup:
    """One group of three rows; row ``i`` ticks ``rates[i]`` times a step
    (1.0: every step).  Messages take one step, as between engines."""

    def __init__(self, rates, quiesce=True):
        self.c = KernelCluster(1, election=ELECTION, heartbeat=1)
        self.c.state = self.c.state._replace(
            quiesce_on=jnp.full((3,), quiesce, bool))
        self.rates = np.ones(3)
        self.acc = np.zeros(3)
        self.ticks = np.zeros(3, int)
        self.c.run_until_leader()
        self.set_rates(rates)

    def set_rates(self, rates):
        """``rates`` by role: the leader's first, then its followers'."""
        lead = self.leader()
        rows = [lead] + [r for r in range(3) if r != lead]
        for row, rate in zip(rows, rates):
            self.rates[row] = rate

    def leader(self):
        row = self.c.leader_row(0)
        assert row is not None, "the group has no leader"
        return row

    def step(self, **kw):
        self.acc += self.rates
        tick = self.acc >= 1.0
        self.acc[tick] -= 1.0
        self.ticks += tick
        return self.c.step(tick=tick, **kw)

    def f(self, name):
        return self.c.field(name).copy()

    def run_idle_until_asleep(self, limit=600):
        """Idle steps until every row sleeps -> per row, its own tick
        count when it entered; the terms and roles seen on the way."""
        entered = [None] * 3
        terms, roles = {tuple(self.f("term"))}, set()
        for _ in range(limit):
            self.step()
            q = self.f("quiesced")
            terms.add(tuple(self.f("term")))
            roles |= set(self.f("role").tolist())
            for row in range(3):
                if q[row] and entered[row] is None:
                    entered[row] = (self.ticks.copy(), row)
            if q.all():
                return entered, terms, roles
        raise AssertionError(
            f"not all asleep after {limit} steps: quiesced "
            f"{self.f('quiesced')}, idle {self.f('idle_tick')}, "
            f"terms {sorted(terms)}")

    def commit_one(self, steps=12):
        lead = self.leader()
        before = self.f("committed")
        self.step(proposals={lead: 1})
        for _ in range(steps):
            self.step()
        after = self.f("committed")
        assert (after == before[lead] + 1).all(), (before, after)


def schedule(seed):
    """Seeded rates, by role (leader first): one row at 1.0, the others in
    [0.7, 1.0]; which role is the fast one moves with the seed."""
    rng = random.Random(seed)
    rates = [1.0, rng.uniform(0.7, 1.0), rng.uniform(0.7, 1.0)]
    rng.shuffle(rates)
    return rates


SCHEDULES = [pytest.param([1.0, 0.7, 0.7], id="leader-fastest"),
             pytest.param([0.7, 1.0, 0.85], id="leader-slowest"),
             pytest.param([1.0, 1.0, 1.0], id="alike")] + [
    pytest.param(schedule(seed), id=f"seed{seed}") for seed in (1, 2, 3, 4)]


@pytest.mark.parametrize("rates", SCHEDULES)
def test_a_group_enters_together_and_no_term_moves(rates):
    """The no-election case: the first row to cross tells the others, they
    follow within ``election_rtt`` of their own ticks, and nobody
    campaigns because its leader went quiet first.  (``leader-fastest``
    and the seeds that put the leader ahead fail on the parent's kernel:
    the term moves.)"""
    g = SkewedGroup(rates)
    g.commit_one()
    lead, term = g.leader(), g.f("term")
    entered, terms, roles = g.run_idle_until_asleep()
    assert terms == {tuple(term)}, f"a term moved across the entry: {terms}"
    assert roles <= {KP.FOLLOWER, KP.LEADER}, f"somebody campaigned: {roles}"
    assert g.leader() == lead
    first = min(e[0].sum() for e in entered)
    first_ticks = next(e[0] for e in entered if e[0].sum() == first)
    for at, row in entered:
        assert at[row] - first_ticks[row] <= ELECTION, (
            f"row {row} entered {at[row] - first_ticks[row]} of its ticks "
            f"after the first (rates {g.rates})")
    # asleep, nothing moves: no heartbeat, no election, for a long while
    epoch = g.f("quiesce_epoch")
    for _ in range(5 * THRESHOLD):
        out = g.step()
    assert g.f("quiesced").all() and (g.f("quiesce_epoch") == epoch).all()
    assert (g.f("term") == term).all()
    assert not np.asarray(out.s_hb).any()


@pytest.mark.parametrize("rates", SCHEDULES[:2] + SCHEDULES[3:5])
def test_a_proposal_a_read_and_a_transfer_each_wake_the_group(rates):
    g = SkewedGroup(rates)
    g.commit_one()
    g.run_idle_until_asleep()
    # a proposal at the leader: everyone wakes, the entry commits
    epoch = g.f("quiesce_epoch")
    g.commit_one()
    assert not g.f("quiesced").any()
    assert (g.f("quiesce_epoch") == epoch + 1).all()
    # a read, once the grace window of the entry has passed: the leader
    # wakes on it, its heartbeat carries the ctx and wakes the followers,
    # the read comes back ready
    g.run_idle_until_asleep()
    for _ in range(2 * ELECTION):
        g.step()
    lead, ready = g.leader(), False
    g.step(reads={lead: (7, 9)})
    for _ in range(8):
        out = g.step()
        ready |= bool(np.asarray(out.rtr_valid)[lead].any())
    assert ready, "the read did not come back"
    assert not g.f("quiesced").any()
    # a transfer: the group wakes, the target leads, and it commits
    g.run_idle_until_asleep()
    lead = g.leader()
    target = next(r for r in range(3) if r != lead)
    g.step(transfers={lead: target + 1})
    for _ in range(12):
        g.step()
    assert g.leader() == target
    assert not g.f("quiesced").any()
    g.commit_one()


def test_a_replica_that_just_left_quiesce_is_not_pulled_back_in():
    """A peer's word that was on its way when the group woke finds rows
    that just served something: they stay awake.  Once a row's own idle
    clock is half way it follows the word (the first to cross speaks for
    rows whose clocks run up to twice as slow)."""
    g = SkewedGroup([1.0, 0.8, 0.7])
    g.commit_one()
    g.run_idle_until_asleep()
    g.commit_one()                      # woke: a proposal, and its commit
    for row in range(3):
        g.c.enqueue(row, Msg(MT.QUIESCE, (row + 1) % 3 + 1, row + 1, 0))
    g.step()
    assert not g.f("quiesced").any(), "pulled back in on a stale word"
    while g.f("idle_tick").min() * 2 < THRESHOLD:
        g.step()
        assert not g.f("quiesced").any()
    follower = next(r for r in range(3) if r != g.leader())
    g.c.enqueue(follower, Msg(MT.QUIESCE, g.leader() + 1, follower + 1, 0))
    g.step()
    assert g.f("quiesced")[follower]


def test_a_heartbeat_wakes_only_after_the_grace_window():
    """quiesce.go:60-89: trailing heartbeats of peers not yet in are
    answered asleep for ``election_rtt`` ticks after the entry; a later
    one wakes the replica (a leader that woke alone brings its group
    out)."""
    g = SkewedGroup([1.0, 1.0, 1.0])
    g.commit_one()
    g.run_idle_until_asleep()
    lead = g.leader()
    follower = next(r for r in range(3) if r != lead)
    term, commit = int(g.f("term")[lead]), int(g.f("committed")[lead])
    beat = Msg(MT.HEARTBEAT, lead + 1, follower + 1, term, commit=commit)
    epoch = g.f("quiesce_epoch")
    g.c.enqueue(follower, beat)
    g.step()
    assert g.f("quiesced").all() and (g.f("quiesce_epoch") == epoch).all()
    g.c.pending[lead].clear()           # its answer, asleep: not our subject
    for _ in range(ELECTION + 1):
        g.step()
    g.c.enqueue(follower, beat)
    g.step()
    assert not g.f("quiesced")[follower]
    assert g.f("quiesce_epoch")[follower] == epoch[follower] + 1


def test_with_quiesce_off_nothing_enters_and_the_word_is_nothing():
    g = SkewedGroup([1.0, 0.7, 0.7], quiesce=False)
    g.commit_one()
    term = g.f("term")
    for _ in range(3 * THRESHOLD):
        out = g.step()
        assert not (np.asarray(out.s_hb_commit) == KP.QUIESCE_WORD).any()
    for row in range(3):
        g.c.enqueue(row, Msg(MT.QUIESCE, (row + 1) % 3 + 1, row + 1, 0))
    g.step()
    assert not g.f("quiesced").any() and (g.f("term") == term).all()
    g.commit_one()


# ---------------------------------------------------------------------------
# Part B: the host path, the kernel path and the mesh path, through NodeHosts
# whose clocks differ by 30%
# ---------------------------------------------------------------------------

RTT_MS = {1: 10, 2: 12, 3: 13}          # host 1's clock is the fastest
PATHS = ["host-path", "kernel-path", "mesh-path"]


class HashedKV(KVStateMachine):
    """``KVStateMachine`` with the hash ``NodeHost.get_sm_hash`` asks for."""

    def get_hash(self) -> int:
        return table_hash(self.kv)


def table_hash(kv: dict) -> int:
    return zlib.crc32("\n".join(
        f"{k}={v}" for k, v in sorted(kv.items())).encode())


def make_hosts(prefix, path):
    """Three NodeHosts with one replica of shard 1 each.  On the mesh path
    they share one ``MeshEngine`` (a ``MeshSpec`` of their own name): the
    group's three replicas are rows on three of the forced host devices."""
    addrs = {i: f"{prefix}-{i}" for i in RTT_MS}
    geometry = dict(kernel_log_cap=256, kernel_capacity=8,
                    kernel_apply_batch=16, kernel_compaction_overhead=16)
    if path == "mesh-path":
        geometry["mesh"] = MeshSpec(name=prefix, g_size=1, replicas=3,
                                    n_local=4)
    hosts = {}
    for rid, addr in addrs.items():
        nh = NodeHost(NodeHostConfig(
            raft_address=addr, rtt_millisecond=RTT_MS[rid],
            expert=ExpertConfig(**geometry)))
        nh.start_replica(addrs, False, HashedKV, Config(
            shard_id=1, replica_id=rid, election_rtt=ELECTION,
            heartbeat_rtt=2, quiesce=True,
            device_resident=path == "kernel-path",
            mesh_resident=path == "mesh-path"))
        hosts[rid] = nh
    return hosts


def shows(nh):
    """-> (quiesced, term, wakes) of host ``nh``'s replica, whichever path;
    ``wakes`` moves every time the replica leaves quiesce."""
    node = nh.nodes[1]
    if node.peer is not None:           # host path: QuiesceState in node.py
        return (node.qs.quiesced(), node.peer.raft.term,
                node.qs.exit_quiesce_tick)
    eng = node.engine                   # its host's own, or the shared mesh
    with eng.mu:
        s, lane = eng.state, node.lane
        return (bool(np.asarray(s.quiesced)[lane]),
                int(np.asarray(s.term)[lane]),
                int(np.asarray(s.quiesce_epoch)[lane]))


def wait_all(hosts, asleep, seconds=20.0):
    deadline = time.time() + seconds
    while time.time() < deadline:
        if all(shows(nh)[0] == asleep for nh in hosts.values()):
            return
        time.sleep(0.02)
    raise AssertionError(
        f"replicas {'not asleep' if asleep else 'not awake'}: "
        f"{ {r: shows(nh) for r, nh in hosts.items()} }")


def at_the_leader(hosts, call, seconds=30.0):
    """``call(the leader's host)`` -> (that host's id, what it returned).
    Retried, as a user must: a request is dropped right after an election,
    and on the host path a follower refuses the word of a leader whose
    clock ran ahead of its own since the last wake (upstream's
    ``_just_exited_quiesce``), campaigns beside the silent leader, and a
    request that meets the election is lost."""
    deadline = time.time() + seconds
    while True:
        lead = wait_leader(hosts, timeout=30)
        try:
            return lead, call(hosts[lead])
        except RequestError:
            if time.time() > deadline:
                raise
            time.sleep(0.1)


def propose(hosts, cmd, written=None):
    """Write ``cmd`` through the leader's host; ``written`` is the plain
    reference, a dict that takes what was acknowledged."""
    value = at_the_leader(hosts, lambda nh: nh.sync_propose(
        nh.get_noop_session(1), cmd, timeout_s=5))[1]
    if written is not None:
        k, v = cmd.decode().split("=", 1)
        written[k] = v
    return value


def read(hosts, key):
    return at_the_leader(
        hosts, lambda nh: nh.sync_read(1, key, timeout_s=3))


def lead_from(hosts, rid, seconds=20.0):
    """Move the leader to host ``rid`` (retrying: a transfer is refused
    while one is outstanding, and dropped right after an election)."""
    deadline = time.time() + seconds
    while wait_leader(hosts, timeout=30) != rid:
        lead = wait_leader(hosts, timeout=30)
        try:
            hosts[lead].request_leader_transfer(1, rid)
        except RequestError:
            pass
        time.sleep(0.3)
        assert time.time() < deadline, "the leader did not move"


def settle(hosts, rid, written, seconds=30.0):
    """Host ``rid`` leads, a write went through it, and all three replicas
    show one term -> {replica: term}.  (Beside busy workers a starved
    follower times out now and then; what is read before the entry has to
    be a group at rest, or the comparison after it says nothing.)"""
    deadline = time.time() + seconds
    while True:
        lead_from(hosts, rid)
        propose(hosts, b"k0=v0", written)
        terms = {r: shows(nh)[1] for r, nh in hosts.items()}
        if len(set(terms.values())) == 1 and (
                wait_leader(hosts, timeout=30) == rid):
            return terms
        assert time.time() < deadline, f"the group did not settle: {terms}"


def late_heartbeat(hosts, leader, follower):
    """A heartbeat of the leader's reaches ``follower`` long after the
    group entered (its grace window, ``election_rtt`` ticks, is over): by
    ``quiesce.go:60-77`` it wakes the replica, whose answer brings the
    group out, and nobody campaigns.  Handed to the replica's own node as a
    transport would hand it over (on the mesh path past the gate that
    turns hub copies of resident links away: this one stands for a
    heartbeat the exchange carried late)."""
    _, term, wakes = shows(hosts[follower])
    hosts[follower].nodes[1].handle_message(pb.Message(
        type=MT.HEARTBEAT, shard_id=1, from_=leader, to=follower, term=term))
    hosts[follower]._kick()
    deadline = time.time() + 10.0
    while shows(hosts[follower])[2] == wakes:
        assert time.time() < deadline, "the late heartbeat woke nobody"
        time.sleep(0.01)


@pytest.mark.parametrize("leader_host", [1, 3],
                         ids=["leader-fastest", "leader-slowest"])
@pytest.mark.parametrize("path", PATHS)
def test_both_paths_show_the_same_group(path, leader_host, one_core):
    """The same story on every path, and what it fixes is the same on
    every path: asleep or awake per replica, no term and no leader moved
    across an entry, the same applied values (the plain dict ``written``)
    and one ``get_sm_hash`` on all three replicas."""
    hosts = make_hosts(f"qg{PATHS.index(path)}{leader_host}", path)
    written: dict[str, str] = {}
    try:
        if path == "mesh-path":
            eng = hosts[1].mesh_engine
            assert eng is not None and all(
                nh.mesh_engine is eng and nh.nodes[1].peer is None
                for nh in hosts.values()), "not one mesh engine"
            assert len(eng.state.term.sharding.device_set) == 3
        # busy for more than a threshold first: upstream's QuiesceState
        # counts a replica's first ``threshold`` ticks as "just exited"
        # (its exit tick starts at 0), and would refuse a peer's word then
        lead_from(hosts, leader_host)
        end = time.time() + 1.5 * THRESHOLD * RTT_MS[3] / 1e3
        i = 0
        while time.time() < end:
            propose(hosts, f"w{i}=v{i}".encode(), written)
            i += 1
            time.sleep(0.05)
        terms = settle(hosts, leader_host, written)
        # all three asleep, soon after the first: the fastest clock needs
        # 1.0 s from the last write and the slowest alone 1.3 s
        wait_all(hosts, asleep=True)
        time.sleep(0.5)
        assert all(shows(nh)[0] for nh in hosts.values())
        assert {r: shows(nh)[1] for r, nh in hosts.items()} == terms, (
            "a term moved across the entry")
        assert wait_leader(hosts, timeout=30) == leader_host
        # a proposal wakes the group and commits; so does a read
        propose(hosts, b"k1=v1", written)
        assert not shows(hosts[leader_host])[0]
        assert hosts[leader_host].stale_read(1, "k1") == "v1"
        wait_all(hosts, asleep=True)
        lead, value = read(hosts, "k1")
        assert value == "v1"
        assert not shows(hosts[lead])[0]
        # a heartbeat that arrives late wakes its replica, the group comes
        # out and goes back to sleep; on the device paths nobody campaigned
        # (the host path re-enters by upstream's rule, under which a
        # follower refuses the word of a leader whose clock ran ahead of
        # its own since the wake and campaigns beside it: ``at_the_leader``)
        wait_all(hosts, asleep=True)
        time.sleep(2 * ELECTION * RTT_MS[3] / 1e3)    # the grace window
        lead = wait_leader(hosts, timeout=30)
        terms = {r: shows(nh)[1] for r, nh in hosts.items()}
        late_heartbeat(hosts, lead, next(r for r in hosts if r != lead))
        wait_all(hosts, asleep=True)
        if path != "host-path":
            assert {r: shows(nh)[1] for r, nh in hosts.items()} == terms, (
                "a term moved after a late heartbeat")
            assert wait_leader(hosts, timeout=30) == lead
        # and a transfer: the target leads, the group commits again
        target = 2
        lead_from(hosts, target)
        propose(hosts, b"k2=v2", written)
        assert hosts[target].stale_read(1, "k2") == "v2"
        # what was applied is what was acknowledged, on all three replicas
        deadline = time.time() + 10.0
        while {nh.get_sm_hash(1) for nh in hosts.values()} != {
                table_hash(written)}:
            assert time.time() < deadline, "the replicas did not converge"
            time.sleep(0.05)
        for nh in hosts.values():
            assert nh.nodes[1].sm.sm.kv == written
    finally:
        for nh in hosts.values():
            nh.close()

"""The every-tenth-round collection as ONE program (core/digest.py) equals the
three it replaces, round for round.

``Shadow`` stands in an engine's ``_collect_digest``: at every collection it
runs ``fleet_stats``, ``fleet_health`` and ``check_invariants`` (the three
programs an engine ran before PR 38, as they still are) on the same state,
the same sender ids and the same carried columns, with the parent's own
reset of the lanes whose occupant changed, and compares their dicts and
their next digests with what the engine decoded from its one packed vector
and kept as its one carried array.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
from dragonboat_tpu.core import digest, fleet, health, invariants, kstate
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.request import RequestError

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_health import _perturb  # noqa: E402
from test_nodehost import KVStateMachine  # noqa: E402


def reference(state, inbox, carry, dirty, thresholds, k, probe):
    """What the three programs give on ``state`` / ``inbox`` and the
    columns of ``carry`` (a host array), the invariant digest's age zeroed
    for the lanes ``dirty`` first: ``(dicts, next carry)``; a part that is
    off gives None and leaves its columns as they are."""
    h, v = digest.split_carry(jnp.asarray(np.asarray(carry)))
    dicts = [fleet.stats_to_dict(fleet.fleet_stats(state, inbox)), None, None]
    if k > 0:
        report, h = health.fleet_health(state, inbox, h,
                                        thresholds=thresholds, k=k)
        dicts[1] = health.report_to_dict(report)
    if probe:
        if dirty:
            v = v._replace(ticks=v.ticks.at[np.array(dirty)].set(0))
        report, v = invariants.check_invariants(state, v)
        dicts[2] = invariants.report_to_dict(report)
    return dicts, np.stack([np.asarray(x) for x in (*h, *v)], axis=1)


class Shadow:
    """One engine's collections (``step_all`` calls ``_collect_digest``
    under the engine lock, right after a step), each beside the reference
    on the same inputs, every verdict kept."""

    def __init__(self, eng, collect) -> None:
        self.eng = eng
        self.compared = 0
        self.mismatches: list = []
        self.fleets: list = []      # every collection's last_fleet
        self.resets = 0             # collections that re-seeded a lane
        self._collect = collect

    def __call__(self) -> None:
        eng = self.eng
        if eng._digest is None:
            eng._digest = eng._make_digest()
        state = eng.state
        inbox = np.array(jax.device_get(eng._fleet_inbox_from()))
        carry = np.array(eng._digest)
        dirty = sorted(eng._inv_dirty) if eng.invariant_probe else []
        seen = eng._inv_violations_seen
        before = (eng.last_health, eng.last_invariants)
        self._collect(eng)
        want, want_carry = reference(
            state, inbox, carry, dirty, eng.health_thresholds,
            eng.health_top_k, eng.invariant_probe)
        if want[2] is not None:
            want[2]["violations_seen"] = seen + want[2]["total"]
        got = [eng.last_fleet,
               eng.last_health if eng.health_top_k > 0 else None,
               eng.last_invariants if eng.invariant_probe else None]
        for name, g, w in zip(("fleet", "health", "invariants"), got, want):
            if g != w:
                self.mismatches.append((self.compared, name, g, w))
        # a part that is off leaves last_* as it found it
        if eng.health_top_k == 0 and eng.last_health is not before[0]:
            self.mismatches.append((self.compared, "health ran"))
        if not eng.invariant_probe and eng.last_invariants is not before[1]:
            self.mismatches.append((self.compared, "invariants ran"))
        got_carry = np.asarray(eng._digest)
        if got_carry.dtype != np.int32 \
                or not np.array_equal(got_carry, want_carry):
            self.mismatches.append((self.compared, "carry",
                                    np.argwhere(got_carry != want_carry)))
        self.fleets.append(eng.last_fleet)
        self.resets += bool(dirty)
        self.compared += 1


def shadow_engines(monkeypatch) -> dict:
    """Every engine's ``_collect_digest`` from here on runs under a
    ``Shadow`` of its own, from its first collection: -> {engine: Shadow}."""
    from dragonboat_tpu.engine.kernel_engine import KernelEngine

    shadows: dict = {}
    collect = KernelEngine._collect_digest

    def shadowed(eng) -> None:
        if eng not in shadows:
            shadows[eng] = Shadow(eng, collect)
        shadows[eng]()

    monkeypatch.setattr(KernelEngine, "_collect_digest", shadowed)
    return shadows


def _wait(cond, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


def _propose(nh, sid, cmd: bytes, timeout=30.0):
    deadline = time.time() + timeout
    while True:
        try:
            return nh.sync_propose(nh.get_noop_session(sid), cmd,
                                   timeout_s=5.0)
        except RequestError:
            if time.time() > deadline:
                raise
            time.sleep(0.05)


CASES = {
    "all-on": dict(),
    "top-k-0": dict(health_top_k=0),
    "probe-off": dict(invariant_probe=False),
    "depth-1": dict(kernel_pipeline_depth=1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_packed_collection_equals_the_three_round_for_round(
        case, monkeypatch):
    """One engine, ``fleet_stats_every`` 5, through an election, steady
    writes, a quiesce entry and a wake, a lane vacated and taken by a new
    occupant: at EVERY collection the decoded ``last_fleet`` /
    ``last_health`` / ``last_invariants`` equal the three programs' dicts
    and the carried array their next digests."""
    prefix = f"dg-{case}-{time.monotonic_ns()}"
    nh = NodeHost(NodeHostConfig(
        raft_address=f"{prefix}-1", rtt_millisecond=5,
        expert=ExpertConfig(kernel_log_cap=64, kernel_capacity=8,
                            fleet_stats_every=5, **CASES[case])))
    shadows = shadow_engines(monkeypatch)
    try:
        def start(sid):
            nh.start_replica({1: f"{prefix}-1"}, False, KVStateMachine,
                             Config(shard_id=sid, replica_id=1,
                                    election_rtt=10, heartbeat_rtt=2,
                                    quiesce=True, device_resident=True))

        for sid in (1, 2, 3):                      # the elections
            start(sid)
        assert _wait(lambda: all(nh.get_leader_id(s)[1] for s in (1, 2, 3)),
                     60), "not every shard elected"
        eng = nh.kernel_engine
        assert _wait(lambda: eng in shadows, 30)
        shadow = shadows[eng]
        for i in range(30):                        # steady writes
            _propose(nh, 1 + i % 3, f"k{i}=v{i}".encode())
        # left alone every group falls asleep (100 idle ticks of 5 ms) ...
        assert _wait(lambda: (eng.last_fleet or {}).get("quiesced") == 3, 60)
        wakes = eng.last_fleet["quiesce_wakes"]
        _propose(nh, 2, b"wake=up")                # ... and a write wakes one
        assert _wait(lambda: eng.last_fleet["quiesce_wakes"] > wakes, 30)
        # a lane vacated, and taken by a new occupant at a lower term
        lane = nh.nodes[3].lane
        nh.stop_replica(3)
        assert _wait(lambda: shadow.fleets
                     and shadow.fleets[-1]["occupied"] == 2, 30)
        start(4)
        assert _wait(lambda: nh.get_leader_id(4)[1], 60)
        assert nh.nodes[4].lane == lane, "the freed lane was not reused"
        for i in range(10):
            _propose(nh, 4, f"n{i}=v{i}".encode())
        done = shadow.compared
        assert _wait(lambda: shadow.compared >= done + 2, 30)
        with eng.mu:
            assert not shadow.mismatches, shadow.mismatches[:3]
            assert shadow.compared >= 10
            seen = shadow.fleets
            ticks = (eng._health_seq, eng._inv_seq)
        assert ticks == (0 if case == "top-k-0" else shadow.compared,
                         0 if case == "probe-off" else shadow.compared)
        # the collections saw the story: candidates or none yet, leaders,
        # sleepers, a wake, the vacated lane and its new occupant
        assert any(f["role_count"]["leader"] == 3 for f in seen)
        assert any(f["quiesced"] == 3 for f in seen)
        assert seen[-1]["quiesce_wakes"] >= 1
        assert any(f["occupied"] == 2 for f in seen)
        assert seen[-1]["occupied"] == 3
        if case != "probe-off":
            assert shadow.resets >= 2, "no collection re-seeded a lane"
            assert eng.last_invariants["violations_seen"] == 0
    finally:
        nh.close()


def _mesh_of_two():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 devices")
    return NamedSharding(Mesh(np.array(devs[:2]), ("g",)), PS("g"))


@pytest.mark.parametrize("boxed", [False, True], ids=["senders", "boxed"])
@pytest.mark.parametrize("k,probe", [(4, True), (0, True), (4, False),
                                     (16, True)])
def test_the_program_on_a_two_device_placement(k, probe, boxed):
    """``digest_program`` (the resident form in, the carried array placed
    along G as the state is) on the two-device CPU mesh, carried across
    randomized perturbations beside the three programs: equal dicts, equal
    next carry, the carry still sharded.  ``boxed``: the sender ids sliced
    out of a packed ``[G, Wi]`` inbox inside the program, as the mesh
    backend hands them over.  ``k`` 16 clamps to the 12 lanes."""
    from tests.kernel_harness import KernelCluster

    rows = _mesh_of_two()
    c = KernelCluster(4, 3)          # G = 12, divisible by 2
    for _ in range(30):
        c.step(tick=True)
    kp, G = c.kp, c.G
    box = c._build_inbox()
    inbox = kstate.pack_columns(kstate.inbox_columns(kp)[0], box._asdict()) \
        if boxed else box.from_
    inbox = jax.device_put(inbox, rows)
    program = digest.digest_program(kp, health.DEFAULT_THRESHOLDS, k, probe,
                                    boxed, rows)
    carry = jax.device_put(digest.empty_carry(G), rows)
    rng = np.random.default_rng(38)
    state = c.state
    for tick in range(5):
        state = _perturb(state, rng)
        resident = jax.device_put(kstate.pack_state(kp, state), rows)
        want, want_carry = reference(
            state, box.from_, np.asarray(carry), [],
            health.DEFAULT_THRESHOLDS, k, probe)
        vec, carry = program(resident, inbox, carry)
        got = digest.decode(np.asarray(vec).tolist(), G, k, probe)
        assert got == want, tick
        assert carry.sharding == rows and carry.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(carry), want_carry)
    assert [d is not None for d in got] == [True, k > 0, probe]
    table, total = digest.layout(G, k, probe)
    assert vec.shape == (total,) and vec.dtype == jnp.int32
    # the static table: fields back to back, in their classes' order
    at = 0
    for cls, fields in table:
        assert [name for name, _, _ in fields] == list(cls._fields)
        for _name, start, shape in fields:
            assert start == at
            at += int(np.prod(shape, dtype=int))
    assert at == total


def test_decode_refuses_a_vector_of_another_layout():
    _table, total = digest.layout(8, 4, True)
    with pytest.raises(ValueError, match="layout"):
        digest.decode([0] * (total - 1), 8, 4, True)
    dicts = digest.decode([0] * total, 8, 4, True)
    assert dicts[0] == {**fleet.empty_dict(), "quiesced_by_word": 0,
                        "quiesce_wakes": 0}
    assert dicts[1] == health.empty_dict()
    assert dicts[2] == invariants.empty_dict()

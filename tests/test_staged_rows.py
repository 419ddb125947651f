"""A round's staging array is cleared where the round before it wrote, and
nowhere else (``_RoundStaging.reset``, PR 43): a row that was written and
not reset would deliver a stale message or proposal twice.

The shadow below rides every ``_RoundStaging`` of the process.  It logs the
builders' calls since the staging's last reset and, at every upload,
replays them into a ZEROED array of the same geometry through the same
builder code (the full-reset form the engine ran before: ``up.fill(0)``,
then the round's writes, a tick round's column as ``tick[lanes] = True``),
and holds the array that goes up to it bit for bit.  It also holds every
reset to the rows the log names, and the engines' counter
``engine_round_swept_rows`` to the resets.  One seeded schedule goes
through it on serial engines at depth 0 and depth 1 (two buffer slots) and
on the mesh engine, whose follower hosts stage their proposals into the
leader's row: messages, proposals, reads, a leader transfer, ticks, a
removal and a re-admission onto the same rows.

A mismatch is recorded, not raised (an upload runs on the engine's own
thread); each chapter ends by asserting that none was.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from dragonboat_tpu.engine import kernel_engine as ke
from dragonboat_tpu.request import RequestError

from test_retire_named import Story, wait_for

_WRITERS = ("prop", "read", "transfer", "tick", "applied")


class Shadow:
    """The full-reset form beside every staging array of the process."""

    def __init__(self, patch: pytest.MonkeyPatch) -> None:
        self.mismatches: list[str] = []
        self.uploads = self.tick_uploads = self.resets = 0
        self.rows_reset = self.forwarded = 0
        self.saw = dict.fromkeys(("add", "tick_all", *_WRITERS), 0)
        self._made: dict[int, tuple] = {}       # id(rows) -> ctor arguments
        self._log: dict[int, list] = {}         # id(rows) -> calls
        self._ref: dict[int, ke._RoundStaging] = {}
        self._swept: dict[int, int] = {}        # id(rows) -> rows reset
        self._orig = {
            "init": ke._RoundStaging.__init__,
            "reset": ke._RoundStaging.reset,
            "tick_all": ke._RoundStaging.tick_all,
            "to_device": ke._RoundStaging.to_device,
            "add": ke._InboxBuilder.add,
            **{w: getattr(ke._InputBuilder, w) for w in _WRITERS},
        }
        shadow, orig = self, self._orig

        def init(st, kp, G, mesh_replicas=None):
            orig["init"](st, kp, G, mesh_replicas=mesh_replicas)
            shadow._made[id(st.rows)] = (kp, G, mesh_replicas)
            shadow._log[id(st.rows)] = []
            shadow._swept[id(st.rows)] = 0

        def reset(st):
            log = shadow._log[id(st.rows)]
            wrote = {c[1] for c in log if c[0] != "tick_all"}
            n = orig["reset"](st)
            if n != len(wrote):
                shadow.mismatches.append(
                    f"a reset cleared {n} rows, the builders wrote "
                    f"{sorted(wrote)}")
            shadow.resets += 1
            shadow.rows_reset += n
            shadow._swept[id(st.rows)] += n
            log.clear()
            return n

        def tick_all(st, nodes):
            shadow._log[id(st.rows)].append(("tick_all", sorted(nodes)))
            return orig["tick_all"](st, nodes)

        def add(box, g, m, n):
            ok = orig["add"](box, g, m, n)
            shadow._log[id(box._rows)].append(("add", g, m, n, ok))
            return ok

        def writer(name):
            def write(inp, g, *args):
                shadow._log[id(inp._rows)].append((name, g, *args))
                return orig[name](inp, g, *args)
            return write

        def to_device(st, sharding=None):
            try:
                shadow.compare(st)
            except Exception as e:                      # noqa: BLE001
                shadow.mismatches.append(f"the shadow itself: {e!r}")
            return orig["to_device"](st, sharding)

        patch.setattr(ke._RoundStaging, "__init__", init)
        patch.setattr(ke._RoundStaging, "reset", reset)
        patch.setattr(ke._RoundStaging, "tick_all", tick_all)
        patch.setattr(ke._RoundStaging, "to_device", to_device)
        patch.setattr(ke._InboxBuilder, "add", add)
        for w in _WRITERS:
            patch.setattr(ke._InputBuilder, w, writer(w))
        stage_props = ke.KernelEngine._stage_props

        def counted(eng, g, n, inp, cc_entry, props):
            shadow.forwarded += eng._prop_target(n)[0] != g
            return stage_props(eng, g, n, inp, cc_entry, props)

        patch.setattr(ke.KernelEngine, "_stage_props", counted)

    def compare(self, st) -> None:
        """The array about to go up against the full-reset form."""
        key = id(st.rows)
        ref = self._ref.get(key)
        if ref is None:
            kp, G, mesh_replicas = self._made[key]
            ref = self._ref[key] = object.__new__(ke._RoundStaging)
            self._orig["init"](ref, kp, G, mesh_replicas=mesh_replicas)
        ref.up.fill(0)                      # the full reset
        ticked = False
        for name, *args in self._log[key]:
            self.saw[name] += 1
            if name == "tick_all":
                ref.inp._tick[np.asarray(args[0], np.int64)] = True
                ticked = True
            elif name == "add":
                g, m, n, ok = args
                if self._orig["add"](ref.inbox, g, m, n) != ok:
                    self.mismatches.append(
                        f"upload {self.uploads}: row {g} took a message "
                        f"{'' if ok else 'not '}where a zeroed row would "
                        "not have")
            else:
                self._orig[name](ref.inp, *args)
        self.uploads += 1
        self.tick_uploads += ticked
        if not np.array_equal(st.up, ref.up):
            r, c = (a[0] for a in np.nonzero(st.up != ref.up))
            self.mismatches.append(
                f"upload {self.uploads}: cell [{r}, {c}] is {st.up[r, c]}, "
                f"the full-reset form has {ref.up[r, c]} "
                f"({int((st.up != ref.up).sum())} cells differ)")

    def swept_by(self, eng) -> int:
        return sum(self._swept[id(st.rows)]
                   for pair in eng._bufs for st in pair)


@pytest.fixture(scope="module", params=[
    pytest.param((kind, depth), id=f"{kind}-depth{depth}")
    for kind, depth in (("serial", 0), ("serial", 1), ("mesh", 0))])
def story(request):
    kind, depth = request.param
    if kind == "mesh":
        import jax

        if len(jax.devices()) < 6:
            pytest.skip("the mesh engine needs 6 devices")
    with pytest.MonkeyPatch.context() as patch:
        shadow = Shadow(patch)
        s = Story(kind, depth)
        s.shadow = shadow
        try:
            s.start(1)
            s.start(2)
            yield s
        finally:
            s.close()


def _clean(s) -> None:
    said = s.shadow.mismatches
    assert not said, "\n".join(said[:10])


def _read(s, rid: int, shard: int, key: str, seconds=30.0):
    """A linearizable read through host ``rid`` (a follower's host forwards
    its ReadIndex to the leader's)."""
    import time

    deadline = time.time() + seconds
    while True:
        try:
            return s.hosts[rid].sync_read(shard, key, timeout_s=5)
        except RequestError:
            assert time.time() < deadline, f"{key!r} was never read"
            time.sleep(0.05)


def test_messages_proposals_reads_a_transfer_and_ticks(story):
    s, sh = story, story.shadow
    rng = random.Random(43)
    for shard in (1, 2):
        s.leader(shard)
    wrote: dict[int, dict] = {1: {}, 2: {}}
    for i in range(40):
        shard = rng.choice((1, 2))
        op = rng.random()
        if op < 0.6 or not wrote[shard]:
            key, val = f"k{rng.randrange(8)}", str(i)
            s.write(shard, f"{key}={val}".encode())
            wrote[shard][key] = val
        elif op < 0.9:
            key = rng.choice(sorted(wrote[shard]))
            assert _read(s, rng.choice((1, 2, 3)), shard, key) \
                == wrote[shard][key]
        else:
            lead = s.leader(shard)
            s.lead_from(shard, rng.choice(
                [r for r in s.hosts if r != lead]))
    lead = s.leader(2)
    s.lead_from(2, next(r for r in s.hosts if r != lead))
    if s.kind == "mesh":
        # a follower's host stages its proposal into the leader's row
        lead = s.leader(1)
        nh = s.hosts[next(r for r in s.hosts if r != lead)]
        before = sh.forwarded
        wait_for(lambda: _proposed(nh, 1, b"fwd=1"), 30,
                 "a follower's host never got a proposal through")
        assert sh.forwarded > before
        # and a cut link's messages come through the hub into the inbox
        # columns (resident links stage none)
        eng, follower = s.engine_of(lead), next(
            r for r in s.hosts if r != lead)
        eng.set_link_hub_served(s.node(follower, 1), lead, True)
        for i in range(5):
            s.write(1, f"cut{i}={i}".encode())
        eng.set_link_hub_served(s.node(follower, 1), lead, False)
        s.write(1, b"healed=1")
    s.settle()
    _clean(s)
    assert sh.uploads > 40 and sh.tick_uploads > 5
    assert sh.tick_uploads < sh.uploads, "no round went up without a tick"
    for what in ("add", "prop", "read", "transfer", "applied", "tick_all"):
        assert sh.saw[what] > 0, f"nothing staged through {what}"


def _proposed(nh, shard: int, cmd: bytes) -> bool:
    try:
        nh.sync_propose(nh.get_noop_session(shard), cmd, timeout_s=5)
        return True
    except RequestError:
        return False


def test_a_removal_and_a_readmission_onto_the_same_rows(story):
    s, sh = story, story.shadow
    s.start(3)
    s.write(3, b"c=1")
    lanes = {rid: s.node(rid, 3).lane for rid in s.hosts}
    ticks0 = sh.tick_uploads
    for nh in s.hosts.values():
        nh.stop_replica(3)              # the rows leave the tick column...
    s.write(1, b"while-empty=1")
    wait_for(lambda: sh.tick_uploads > ticks0 + 2, 30, "no tick round")
    s.start(4)                          # ...and come back under a new group
    assert {rid: s.node(rid, 4).lane for rid in s.hosts} == lanes
    s.write(4, b"d=1")
    assert _read(s, 2, 4, "d") == "1"
    s.write(1, b"after=1")
    ticks1 = sh.tick_uploads
    wait_for(lambda: sh.tick_uploads > ticks1 + 2, 30, "no tick round")
    s.settle()
    _clean(s)


def test_the_counter_counts_the_rows_the_resets_cleared(story):
    s, sh = story, story.shadow
    engines = {id(s.engine_of(rid)): s.engine_of(rid) for rid in s.hosts}
    assert len(engines) == (1 if s.kind == "mesh" else 3)
    for eng in engines.values():
        with eng.mu:                    # between rounds
            counted, swept = eng._swept_rows.value(), sh.swept_by(eng)
        assert counted == swept > 0, (eng.label, counted, swept)
        # a round's reset follows what was staged, not what is held: far
        # fewer rows than rounds x capacity
        assert swept < sh.resets * eng.capacity / 2
    assert sh.rows_reset == sum(sh.swept_by(e) for e in engines.values())
    _clean(s)

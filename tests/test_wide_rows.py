"""Width alone: the device programs an engine of 4,096 lanes runs (the round
``core/round.py`` ``step``, the admission program ``kstate.inject_program``,
the every-tenth-round collection ``core/digest.py``) give, row for row and
bit for bit, what the same programs give at 8 rows holding the same lanes.

Every row's result depends on its own row of the state, of the inbox and of
the input and on nothing else, so a row of a tall state must read what the
same row reads alone.  What can break that is the compiler: jax 0.9.0 on a
v5e dropped vmapped scalar-index scatters on sub-32-bit operands past ~3k
rows of ONE program (README "The TPU design").  So the lanes watched sit at
rows 0, 1,023, 3,071, 3,072 and 4,095 among 4,096 live rows, every row of
the tall state is also held against the same program run a quarter (1,024
rows, the height every served cell has run) at a time (on the chip, where
the hazard lives; tier-1's clock has the eight rows), and the reductions of
the collection against a recount in numpy.

Here on the CPU at a small ring; ``scripts/check_wide_rows.py`` runs the
same functions on the chip at the KernelParams a NodeHost picks there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.core import digest, fleet, health, kernel, params as KP
from dragonboat_tpu.core import round as cround
from dragonboat_tpu.core import kstate
from dragonboat_tpu.core.kstate import ResidentState, ShardState

MT = pb.MessageType

WIDE = 4096
QUARTER = 1024
#: the lanes the issue names, and three neighbours to fill eight rows
WATCHED = (0, 1, 1023, 3071, 3072, 3073, 4094, 4095)

#: the tier-1 geometry: a NodeHost's widths (P 5, K 8, E 8, B 8, RI 4) on a
#: small ring, so the 4,096-row programs compile and run in seconds
CPU_KP = KP.KernelParams(
    num_peers=5, log_cap=64, inbox_cap=8, msg_entries=8, proposal_cap=8,
    readindex_cap=4, apply_batch=16, compaction_overhead=8)


def fresh_state(kp, rows: int) -> ShardState:
    """``rows`` live lanes: replica ``g % 3 + 1`` of a three-replica group
    each (every row occupied, every row its own seed)."""
    rids = (np.arange(rows, dtype=np.int32) % 3) + 1
    pids = np.zeros((rows, kp.num_peers), np.int32)
    pids[:, :3] = (1, 2, 3)
    return kstate.init_state(kp, rows, rids, pids)


def take_rows(tree, rows):
    idx = np.asarray(rows)
    return jax.tree.map(lambda x: jnp.asarray(np.asarray(x)[idx]), tree)


def host_state(kp, res: ResidentState) -> dict:
    """The resident columns as numpy fields (no program: a slice a field)."""
    cols = np.asarray(res.cols)
    out = {c.field: kstate.column_value(c, cols)
           for c in kstate.state_columns(kp)[0]}
    out["lt"] = np.asarray(res.lt)
    return out


def draw_upload(kp, rng, s: dict, proposing: bool) -> np.ndarray:
    """One step's [G, Wu] upload, drawn by ``rng`` from what each row holds
    (``s``: ``host_state``): protocol-shaped traffic for every row.  Rows
    ``g % 4 == 0`` hear nothing from a leader, so they time out, campaign,
    are granted their votes and lead (proposals, acknowledgements, commits,
    apply windows); the others follow a peer that appends, commits and
    beats; a few are asked for a vote at a higher term."""
    G = s["term"].shape[0]
    rc = kstate.round_columns(kp)
    up = np.zeros((G, rc.up_width), np.int32)
    v = kstate.column_views(rc.up, up)
    K, E, B = kp.inbox_cap, kp.msg_entries, kp.proposal_cap
    g = np.arange(G)
    rid, term, last = s["replica_id"], s["term"], s["last"]
    peer = rid % 3 + 1                      # another replica of the group
    other = (rid + 1) % 3 + 1               # and the third
    last_term = np.where(
        last > 0, s["lt"][g, last & (kp.log_cap - 1)], 0)
    leads = g % 4 == 0
    is_leader = s["role"] == KP.LEADER
    is_cand = s["role"] == KP.CANDIDATE

    def put(slot, on, mtype, frm, **fields):
        v["mtype"][on, slot] = mtype
        v["from_"][on, slot] = frm[on]
        for name, val in fields.items():
            v[name][on, slot] = np.broadcast_to(val, (G,))[on]

    # slots 0, 1 (resp): what a candidate and a leader are answered
    on = is_cand & (rng.random(G) < 0.6)
    put(0, on, MT.REQUEST_VOTE_RESP, peer, term=term)
    on = is_leader & (rng.random(G) < 0.7)
    put(0, on, MT.REPLICATE_RESP, peer, term=term,
        log_index=np.maximum(last - rng.integers(0, 3, G), 0))
    on = is_leader & (rng.random(G) < 0.4)
    put(1, on, MT.HEARTBEAT_RESP, other, term=term)
    # slot 2 (rep): a leader's append at the row's own tail
    lead_term = np.maximum(term, 1)
    on = ~leads & ~is_leader & (rng.random(G) < 0.5)
    n_ent = rng.integers(1, E + 1, G)
    put(2, on, MT.REPLICATE, peer, term=lead_term, log_index=last,
        log_term=last_term, n_ent=n_ent,
        commit=np.maximum(last - rng.integers(0, 4, G), 0))
    v["ent_term"][on, 2] = np.where(
        np.arange(E)[None, :] < n_ent[:, None], lead_term[:, None], 0)[on]
    v["ent_cc"][on, 2] = 0
    # slot 3 (hb)
    on = ~leads & ~is_leader & (rng.random(G) < 0.4)
    put(3, on, MT.HEARTBEAT, peer, term=lead_term,
        commit=np.minimum(s["committed"] + 1, last))
    # slot 4 (vote): a rival at a higher term, rarely
    on = ~leads & (rng.random(G) < 0.03)
    put(4, on, MT.REQUEST_VOTE, other, term=term + 1, log_index=last + 5,
        log_term=term + 1)
    # slot 5 (any): a second acknowledgement for a leader
    if K > 5:
        on = is_leader & (rng.random(G) < 0.3)
        put(5, on, MT.REPLICATE_RESP, other, term=term, log_index=last)
    # the input: a tick a step, proposals on leaders, the RSM keeping up
    v["tick"][:] = 1
    v["applied"][:] = s["processed"]
    if proposing:
        n = np.where(is_leader, rng.integers(0, B + 1, G), 0)
        v["prop_valid"][:] = np.arange(B)[None, :] < n[:, None]
    return up


def assert_rows_equal(tall, short, rows, what: str) -> None:
    """Every array of ``tall`` at ``rows`` is, bit for bit, ``short``."""
    idx = np.asarray(rows)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(tall),
                            jax.tree.leaves(short)):
        a, b = np.asarray(a)[idx], np.asarray(b)
        if not np.array_equal(a, b):
            bad = np.argwhere(a != b)
            raise AssertionError(
                f"{what}{jax.tree_util.keystr(path)}: {len(bad)} cells of "
                f"the tall program's rows differ from the short one's; "
                f"first at row {idx[bad[0][0]]}, cell {bad[0][1:]}: "
                f"{a[tuple(bad[0])]} against {b[tuple(bad[0])]}")


def compare_round(kp, steps: int, seed: int, rows: int = WIDE,
                  quarter: int = QUARTER) -> dict:
    """Drive the round at ``rows`` rows, at 8 rows (``WATCHED``) and, where
    ``quarter`` is not 0, a quarter of the rows at a time, on one seeded
    schedule drawn from the tall state; hold state and download of every
    step row for row.
    -> what the tall state went through (so no comparison is of nothing)."""
    rng = np.random.default_rng(seed)
    tall = jax.jit(lambda s: kstate.pack_state(kp, s))(fresh_state(kp, rows))
    short = take_rows(tall, WATCHED)
    quarters = range(0, rows, quarter) if quarter else ()
    parts = [take_rows(tall, range(q, q + quarter)) for q in quarters]
    seen = {"leaders": 0, "committed": 0, "appended": 0, "terms": 0}
    for step_no in range(steps):
        up = draw_upload(kp, rng, host_state(kp, tall), step_no >= steps // 2)
        tall, down = cround.step(kp, kernel.step, tall, jnp.asarray(up))
        short, down8 = cround.step(
            kp, kernel.step, short, jnp.asarray(up[list(WATCHED)]))
        what = f"step {step_no}: "
        assert_rows_equal((tall, down), (short, down8), WATCHED,
                          what + "against 8 rows ")
        for i, q in enumerate(quarters):
            parts[i], down_q = cround.step(
                kp, kernel.step, parts[i], jnp.asarray(up[q:q + quarter]))
            assert_rows_equal((tall, down), (parts[i], down_q),
                              range(q, q + quarter),
                              what + f"against rows {q}-{q + quarter - 1} ")
    s = host_state(kp, tall)
    top = np.arange(rows) >= rows - rows // 4
    seen["leaders"] = int((s["role"][top] == KP.LEADER).sum())
    seen["committed"] = int((s["committed"][top] > 0).sum())
    seen["appended"] = int((s["last"][top] > 0).sum())
    seen["terms"] = int(s["term"][top].max())
    seen["state"] = tall
    return seen


def compare_inject(kp, seed: int, rows: int = WIDE) -> int:
    """Admit a batch into rows of the top quarter (and a few below) of a
    tall state holding live rows everywhere else; the same program at 8
    rows, eight admissions at a time, says what each admitted row must
    read; every other row must read what it read before.  -> admitted."""
    rng = np.random.default_rng(seed)
    program = kstate.inject_program(kp)
    before = jax.jit(lambda s: kstate.pack_state(kp, s))(
        fresh_state(kp, rows))
    lanes = np.sort(np.concatenate([
        rng.choice(np.arange(rows - QUARTER, rows), 504, replace=False),
        rng.choice(np.arange(rows - QUARTER), 8, replace=False)]))
    n = len(lanes)                           # 512: a power of two, unpadded
    P, CAP = kp.num_peers, kp.log_cap
    tail = rng.integers(0, 6, n)
    batch = {
        "replica_id": rng.integers(1, 4, n), "seed": rng.integers(1, 1 << 30, n),
        "rand_timeout": rng.integers(10, 20, n),
        "e_timeout": np.full(n, 10), "h_timeout": np.full(n, 2),
        "check_quorum": rng.random(n) < 0.5, "pre_vote": rng.random(n) < 0.5,
        "quiesce_on": rng.random(n) < 0.5,
        "role": rng.choice([KP.FOLLOWER, KP.NON_VOTING, KP.WITNESS], n),
        "term": rng.integers(1, 9, n), "vote": rng.integers(0, 4, n),
        "applied": tail // 2, "snap_index": np.zeros(n, np.int64),
        "snap_term": np.zeros(n, np.int64), "last": tail,
        "committed": tail // 2,
        "pid": np.tile(np.arange(1, P + 1), (n, 1)),
        "kind": rng.integers(0, 4, (n, P)),
        "lt": rng.integers(0, 9, (n, CAP)),
        "lcc": rng.random((n, CAP)) < 0.3,
    }
    batch = {k: (v if v.dtype == bool else v.astype(np.int32))
             for k, v in batch.items()}
    dev = lambda d: {k: jnp.asarray(v) for k, v in d.items()}  # noqa: E731
    after = program(before, jnp.asarray(lanes.astype(np.int32)), dev(batch))
    blank = take_rows(before, range(8))
    eight = jnp.arange(8, dtype=jnp.int32)
    for at in range(0, n, 8):
        want = program(blank, eight,
                       dev({k: v[at:at + 8] for k, v in batch.items()}))
        assert_rows_equal(after, want, lanes[at:at + 8],
                          f"admissions {at}-{at + 7} ")
    untouched = np.setdiff1d(np.arange(rows), lanes)
    assert_rows_equal(after, take_rows(before, untouched), untouched,
                      "rows no admission named ")
    return n


def compare_collection(kp, tall: ResidentState, seed: int) -> dict:
    """The collection over a tall state that has lived (``compare_round``'s):
    its per-row part, the carried [G, 17] array, against the same program
    at 8 rows, twice (the second collection reads the first's carry); its
    reductions against a recount in numpy.  -> the fleet report."""
    rng = np.random.default_rng(seed)
    rows = tall.cols.shape[0]
    program = digest.digest_program(
        kp, health.DEFAULT_THRESHOLDS, health.DEFAULT_TOP_K, True)
    senders = (rng.random((rows, kp.inbox_cap)) < 0.3) * rng.integers(
        1, 4, (rows, kp.inbox_cap))
    senders = senders.astype(np.int32)
    carry, carry8 = digest.empty_carry(rows), digest.empty_carry(8)
    short = take_rows(tall, WATCHED)
    for turn in range(2):
        vec, carry = program(tall, jnp.asarray(senders), carry)
        _vec8, carry8 = program(
            short, jnp.asarray(senders[list(WATCHED)]), carry8)
        assert_rows_equal(carry, carry8, WATCHED,
                          f"collection {turn}: the carried array ")
    report = digest.decode(np.asarray(vec).tolist(), rows,
                           health.DEFAULT_TOP_K, True)[0]
    s = host_state(kp, tall)
    occ = (s["kind"] != KP.K_ABSENT).any(axis=1)
    assert report["occupied"] == int(occ.sum()) == rows
    assert report["role_count"] == {
        name: int((occ & (s["role"] == i)).sum())
        for i, name in enumerate(fleet.ROLE_NAMES)}
    assert report["leaderless"] == int(
        (occ & (s["leader"] == KP.NO_LEADER)).sum())
    assert report["term_max"] == int(s["term"].max())
    assert report["term_min"] == int(s["term"].min())
    lag = s["committed"] - s["applied"]
    assert list(report["lag_hist"].values()) == [
        int((lag <= b).sum()) for b in fleet.LAG_BUCKETS] + [rows]
    occupancy = (senders != 0).sum(axis=1)
    assert list(report["inbox_hist"].values()) == [
        int((occupancy <= b).sum()) for b in fleet.INBOX_BUCKETS] + [rows]
    return report


# -- tier-1, on the CPU --------------------------------------------------------

@pytest.fixture(scope="module")
def lived():
    """One run of the round comparison for the module: the tall state it
    ends in is what the collection is held over."""
    return compare_round(CPU_KP, steps=32, seed=4096, quarter=0)


def test_the_round_at_4096_rows_is_the_round_at_8_rows(lived):
    """And the schedule was no idle one: in the top quarter alone rows led,
    appended, committed, and terms moved."""
    assert lived["leaders"] >= 100, lived
    assert lived["appended"] >= 700 and lived["committed"] >= 300, lived
    assert lived["terms"] >= 2, lived


def test_admissions_into_the_top_quarter_are_the_rows_admitted():
    assert compare_inject(CPU_KP, seed=1) == 512


def test_the_collection_at_4096_rows_counts_and_carries_every_row(lived):
    report = compare_collection(CPU_KP, lived["state"], seed=7)
    assert report["role_count"]["leader"] >= 400, report


def test_the_watched_rows_are_the_ones_the_issue_names():
    assert {0, 1023, 3071, 3072, 4095} <= set(WATCHED) and len(WATCHED) == 8
    assert max(WATCHED) == WIDE - 1 and QUARTER * 4 == WIDE

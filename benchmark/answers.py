"""Gather what the deployment answered once the window has closed and the
load has drained, and hold it to the reference (``checker.py``).

Five checks, every number printed beside its limit by the caller: (a) every
acknowledged write is held by all three replicas' state machines, and they
agree; (b) a seeded sample of acknowledged keys reads back linearizably from
the leader's host and a follower's; (c) ``get_sm_hash`` agrees across the
replicas of every shard; (d) where the mix reads, a seeded sample of keys'
recorded histories is linearizable; (e) the LogDB's fsync count in the window
is above zero (that it is ``sharded-tan`` on disk is checked at start-up).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

from dragonboat_tpu.request import RequestDroppedError

from benchmark import checker
from benchmark import traffic as gen

READ_BACK_PER_SHARD = 2
READ_BACK_LEAST = 16
HISTORY_KEYS_MOST = 2048
CONVERGE_DEADLINE_S = 60.0
CONTROL_CONVERGE_S = 5.0     # a control never converges: do not wait long


def ops_by_key(records, kinds) -> dict:
    """{(shard, key): [checker.Op]} of the records of ``kinds`` that may
    have taken effect (a timed-out one is open: outcome unknown)."""
    out: dict = {}
    for r in records:
        if r.kind in kinds and r.status != gen.FAILED:
            out.setdefault((r.shard, r.key), []).append(checker.Op(
                r.kind, r.value, r.call_ns,
                r.ret_ns if r.status == gen.OK else None))
    return out


def acknowledged(by_key: dict) -> list:
    return sorted(k for k, ws in by_key.items()
                  if any(w.ret is not None for w in ws))


def converge(dep, by_key: dict, deadline_s: float) -> dict:
    """Wait (bounded) until every replica holds an allowed value for every
    acknowledged key and the replicas agree; -> the last comparison."""
    acked = acknowledged(by_key)
    deadline = time.monotonic() + deadline_s
    while True:
        copies = {rid: {k: dep.replica_value(rid, *k) for k in acked}
                  for rid in dep.hosts}
        found = checker.check_replica_copies(by_key, copies)
        if not (found["lost"] or found["diverging"]) \
                or time.monotonic() > deadline:
            found["keys"] = len(acked)
            return found
        time.sleep(0.2)


def unequal_hashes(dep, deadline_s: float) -> list[int]:
    """Shards whose three replicas' state machines hash differently, once
    given time to converge: a proposal the client gave up on (dropped, then
    retried) may still be on its way to a follower."""
    deadline = time.monotonic() + deadline_s
    while True:
        unequal = [sid for sid in dep.shards
                   if len(set(dep.sm_hashes(sid))) > 1]
        if not unequal or time.monotonic() > deadline:
            return unequal
        time.sleep(0.2)


def sync_read(dep, rid: int, sid: int, key: str, deadline_s: float = 60.0):
    """A linearizable read, retried past the transient not-ready error."""
    end = time.monotonic() + deadline_s
    while True:
        try:
            return dep.hosts[rid].sync_read(sid, key, timeout_s=30)
        except RequestDroppedError:
            if time.monotonic() > end:
                raise
            time.sleep(0.05)


def read_back(dep, by_key: dict, seed: int):
    """A seeded sample of acknowledged keys, read linearizably from the
    leader's host and one follower's host."""
    most = max(READ_BACK_LEAST, READ_BACK_PER_SHARD * len(dep.shards))
    sample = checker.sample_keys(acknowledged(by_key), seed, most)
    leads = {sid: dep.leader_host(sid) for sid in {sid for sid, _ in sample}}
    jobs = [((sid, key), rid) for sid, key in sample
            for rid in (leads[sid], leads[sid] % dep.replicas + 1)]

    def one(job):
        (sid, key), rid = job
        return (sid, key), rid, sync_read(dep, rid, sid, key)

    with ThreadPoolExecutor(max_workers=min(48, max(1, len(jobs)))) as pool:
        reads = list(pool.map(one, jobs))
    return reads, checker.check_read_back(reads, by_key)


def history_check(records, initial: dict, seed: int):
    """A seeded sample of keys (the busiest key always in it): is each
    key's whole recorded history linearizable?"""
    ops = ops_by_key(records, (gen.WRITE, gen.READ))
    if not ops:
        return 0, []
    busiest = max(ops, key=lambda k: len(ops[k]))
    keys = checker.sample_keys(ops, seed, HISTORY_KEYS_MOST, always=[busiest])
    return len(keys), checker.check_histories(
        {k: ops[k] for k in keys}, initial)


def check_answers(dep, traffic: dict, records, initial: dict, seed: int,
                  fsyncs_in_window: int, control: bool) -> list[dict]:
    """-> [{"check", "value", "limit", "rule", ...}] for checker.verdict."""
    converge_s = CONTROL_CONVERGE_S if control else CONVERGE_DEADLINE_S
    t0 = time.monotonic()
    by_key = ops_by_key(records, (gen.WRITE,))
    copies = converge(dep, by_key, converge_s)
    reads, wrong = read_back(dep, by_key, seed)
    unequal = unequal_hashes(dep, converge_s)
    numbers = [
        {"check": "lost_acknowledged_writes", "value": len(copies["lost"]),
         "limit": 0, "rule": "max", "of": copies["keys"],
         "eg": copies["lost"][:3]},
        {"check": "keys_replicas_disagree_on",
         "value": len(copies["diverging"]), "limit": 0, "rule": "max",
         "of": copies["keys"], "eg": copies["diverging"][:3]},
        {"check": "wrong_linearizable_read_backs", "value": len(wrong),
         "limit": 0, "rule": "max", "of": len(reads), "eg": wrong[:3]},
        {"check": "shards_with_unequal_sm_hash", "value": len(unequal),
         "limit": 0, "rule": "max", "of": len(dep.shards),
         "eg": unequal[:3]},
        {"check": "fsyncs_in_window", "value": fsyncs_in_window, "limit": 1,
         "rule": "min"},
    ]
    if traffic.get("read_share", 0.0) > 0:
        checked, bad = history_check(records, initial, seed)
        numbers.append(
            {"check": "keys_with_nonlinearizable_history", "value": len(bad),
             "limit": 0, "rule": "max", "of": checked, "eg": bad[:3]})
    numbers.append({"check": "seconds_the_checks_took",
                    "value": time.monotonic() - t0, "limit": 60.0,
                    "rule": "max"})
    return numbers

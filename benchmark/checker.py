"""The comparison that decides ``correct``, on plain data (no hosts, no jax).

The plain reference is a dict fed the acknowledged writes, plus this
module's copy of the repo's Wing-and-Gong register checker
(``dragonboat_tpu/history.py:check_linearizable_kv``; the original stays).
``run.py`` gathers what the deployment answered and hands it here; every
number compared is exact, so every limit is 0 (or, for the fsync count, a
floor of 1).
"""

from __future__ import annotations

import random
from typing import NamedTuple

INF = float("inf")


class Op(NamedTuple):
    """One client operation on one key (a register)."""
    kind: str                 # "write" | "read"
    value: object             # written value, or the value the read returned
    call: float
    ret: float | None         # None: outcome unknown (timed out, may apply)


def possibly_last(writes: list[Op]) -> set:
    """Values a converged replica may hold for a key: those of writes that
    no acknowledged write was called after the return of."""
    latest_call = max((w.call for w in writes if w.ret is not None),
                      default=-INF)
    return {w.value for w in writes
            if w.ret is None or w.ret >= latest_call}


def check_replica_copies(writes_by_key: dict, copies: dict) -> dict:
    """``writes_by_key``: {key: [Op writes, acknowledged or unknown]};
    ``copies``: {replica: {key: value held}}.  Only keys with at least one
    acknowledged write are held to anything.
    -> {"lost": keys some replica holds no allowed value for,
        "diverging": keys the replicas disagree on}."""
    lost, diverging = [], []
    for key, writes in writes_by_key.items():
        if not any(w.ret is not None for w in writes):
            continue
        allowed = possibly_last(writes)
        held = [table.get(key) for table in copies.values()]
        if any(v not in allowed for v in held):
            lost.append(key)
        if len(set(held)) > 1:
            diverging.append(key)
    return {"lost": lost, "diverging": diverging}


def check_read_back(reads, writes_by_key: dict) -> list:
    """``reads``: [(key, host, value)] taken linearizably after every write
    has returned.  -> the reads that returned a value no last write left."""
    return [(key, host, value) for key, host, value in reads
            if value not in possibly_last(writes_by_key[key])]


def sample_keys(keys, seed: int, most: int, always=()) -> list:
    """A seeded sample of at most ``most`` keys, ``always`` first."""
    rest = sorted(set(keys) - set(always))
    random.Random(f"{seed}:sample").shuffle(rest)
    return (list(always) + rest)[:max(most, len(always))]


def check_histories(ops_by_key: dict, initial_by_key: dict) -> list:
    """-> the keys whose recorded history is not linearizable."""
    return [key for key, ops in ops_by_key.items()
            if not linearizable_register(ops, initial_by_key.get(key))]


def linearizable_register(ops: list[Op], initial) -> bool:
    """Wing & Gong search with memoization over (done-set, value).  An open
    op (``ret is None``) may linearize at any point after its call, or
    never.  Exponential in the worst case: for test-sized histories."""
    n = len(ops)
    if n == 0:
        return True
    ops = sorted(ops, key=lambda o: o.call)
    ends = [o.ret if o.ret is not None else INF for o in ops]

    def minimal(done: frozenset) -> list[int]:
        """Ops not done whose every predecessor is done."""
        first_end = min((ends[j] for j in range(n) if j not in done),
                        default=INF)
        return [i for i in range(n)
                if i not in done and ops[i].call <= first_end]

    def choices(done: frozenset, value):
        for i in minimal(done):
            o = ops[i]
            if o.kind == "write":
                yield done | {i}, o.value
                if o.ret is None:
                    yield done | {i}, value   # an open write may never apply
            elif o.ret is None or o.value == value:
                yield done | {i}, value

    seen = {(frozenset(), initial)}
    stack = [choices(frozenset(), initial)]
    while stack:
        advanced = False
        for done, value in stack[-1]:
            if len(done) == n:
                return True
            if (done, value) in seen:
                continue
            seen.add((done, value))
            stack.append(choices(done, value))
            advanced = True
            break
        if not advanced:
            stack.pop()
    return False


def verdict(numbers: list[dict]) -> bool:
    """``numbers``: [{"check", "value", "limit", "rule"}] with rule
    ``"max"`` (value <= limit) or ``"min"`` (value >= limit)."""
    return all(n["value"] <= n["limit"] if n["rule"] == "max"
               else n["value"] >= n["limit"] for n in numbers)

"""The comparison that decides ``correct`` must be able to fail: a lost
acknowledged write, a stale read and a diverging replica are each flagged,
and the clean history passes."""

import random

import pytest

from benchmark import checker
from benchmark.checker import Op
from dragonboat_tpu import history


def clean():
    """Two keys, three replicas: k1 written twice in sequence, k2 once."""
    writes = {
        "k1": [Op("write", "a", 0, 10), Op("write", "b", 20, 30)],
        "k2": [Op("write", "x", 5, 15)],
    }
    copies = {rid: {"k1": "b", "k2": "x"} for rid in (1, 2, 3)}
    return writes, copies


def test_clean_passes():
    writes, copies = clean()
    assert checker.check_replica_copies(writes, copies) == {
        "lost": [], "diverging": []}
    reads = [("k1", 1, "b"), ("k1", 2, "b"), ("k2", 3, "x")]
    assert checker.check_read_back(reads, writes) == []
    ops = {"k1": writes["k1"] + [Op("read", "b", 40, 50)]}
    assert checker.check_histories(ops, {}) == []


def test_lost_acknowledged_write_is_flagged():
    writes, copies = clean()
    for table in copies.values():
        table["k1"] = "a"          # the second acknowledged write is gone
    found = checker.check_replica_copies(writes, copies)
    assert found["lost"] == ["k1"] and found["diverging"] == []
    for table in copies.values():
        del table["k2"]            # never applied anywhere
    assert checker.check_replica_copies(writes, copies)["lost"] == [
        "k1", "k2"]


def test_diverging_replica_is_flagged():
    writes, copies = clean()
    copies[3]["k1"] = "a"
    found = checker.check_replica_copies(writes, copies)
    assert found["diverging"] == ["k1"] and found["lost"] == ["k1"]


def test_stale_read_is_flagged():
    writes, _ = clean()
    assert checker.check_read_back([("k1", 2, "a")], writes) == [
        ("k1", 2, "a")]
    stale = {"k1": writes["k1"] + [Op("read", "a", 40, 50)]}
    assert checker.check_histories(stale, {}) == ["k1"]
    # a read of the initial value after an acknowledged write
    assert checker.check_histories(
        {"k": [Op("write", "n", 0, 1), Op("read", "old", 2, 3)]},
        {"k": "old"}) == ["k"]


def test_unknown_outcome_may_or_may_not_apply():
    writes = {"k": [Op("write", "a", 0, 10), Op("write", "t", 20, None)]}
    for held in ("a", "t"):
        copies = {rid: {"k": held} for rid in (1, 2, 3)}
        assert checker.check_replica_copies(writes, copies)["lost"] == []
    # a key with only unknown writes is held to nothing
    assert checker.check_replica_copies(
        {"k": [Op("write", "t", 0, None)]}, {1: {}}) == {
            "lost": [], "diverging": []}


def test_concurrent_writes_either_may_be_last():
    writes = {"k": [Op("write", "a", 0, 10), Op("write", "b", 5, 8)]}
    assert checker.possibly_last(writes["k"]) == {"a", "b"}
    later = writes["k"] + [Op("write", "c", 11, 12)]
    assert checker.possibly_last(later) == {"c"}


def test_verdict_rules():
    ok = [{"value": 0, "limit": 0, "rule": "max"},
          {"value": 3, "limit": 1, "rule": "min"}]
    assert checker.verdict(ok)
    assert not checker.verdict(ok + [{"value": 1, "limit": 0, "rule": "max"}])
    assert not checker.verdict([{"value": 0, "limit": 1, "rule": "min"}])


def test_sample_is_seeded_and_keeps_always():
    keys = [f"k{i}" for i in range(100)]
    a = checker.sample_keys(keys, 7, 10, always=["k99"])
    assert a == checker.sample_keys(keys, 7, 10, always=["k99"])
    assert a[0] == "k99" and len(a) == 10
    assert a != checker.sample_keys(keys, 8, 10, always=["k99"])


@pytest.mark.parametrize("seed", range(8))
def test_copy_agrees_with_the_repo_checker(seed):
    """The benchmark's copy of the Wing-and-Gong search and the original
    (``dragonboat_tpu/history.py``) give the same verdict on random small
    register histories, sound and corrupted alike."""
    rng = random.Random(seed)
    for _ in range(60):
        t, value, mine, theirs = 0.0, None, [], []
        for i in range(rng.randrange(2, 7)):
            start = t + rng.random()
            end = start + rng.random() * 3
            t = start if rng.random() < 0.5 else end   # overlap half the time
            if rng.random() < 0.5:
                value = f"v{i}"
                kind, val = "write", value
            else:
                kind = "read"
                val = value if rng.random() < 0.8 else "stale"
            open_op = rng.random() < 0.15
            mine.append(Op(kind, val, start, None if open_op else end))
            theirs.append(history.Op(
                process=0, op=kind, key="k", value=val, call=start,
                ret=None if open_op else end,
                ok=None if open_op else True))
        assert checker.linearizable_register(mine, None) == \
            history.check_linearizable_kv(theirs)

"""Small-scope exhaustive model checker (scripts/model_check.py): the
fast scope must be violation-free on the real kernel, the seeded
protocol bugs it owns must be caught within that same scope, and the
mutation catalogue must track the kernel source (a drifted find-snippet
is a silently-dead mutation test, so it raises instead)."""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np
import pytest

from dragonboat_tpu import raftpb as pb
from dragonboat_tpu.core import params as KP

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "model_check_under_test",
        os.path.join(REPO, "scripts", "model_check.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


mc = _load()


def test_fast_scope_clean_on_real_kernel():
    """BFS over all interleavings within the fast bounds, transition
    relation = the real jitted kernel step: zero violations, and the
    run must actually cover a nontrivial state count."""
    res = mc.run_scope("fast")
    assert res["violations"] == [], res["violations"]
    assert res["scope_complete"]
    assert res["states_explored"] >= 100
    assert res["transitions"] >= res["states_explored"] // 2
    assert set(res["properties"]) >= {
        "election_safety", "leader_append_only", "log_matching",
        "leader_completeness", "state_machine_safety"}


@pytest.mark.parametrize("mutation,expect", [
    # the checker OWNS double_vote (no store-shape signature for the
    # static pass to key on); commit_without_quorum is also caught here
    # (defense in depth on top of its static RS002 owner)
    ("double_vote", "vote_once_per_term"),
    ("commit_without_quorum", "leader_commit_quorum"),
])
def test_checker_catches_mutation(mutation, expect):
    res = mc.run_scope("fast", mutation=mutation)
    assert res["violations"], f"{mutation} escaped the fast scope"
    names = " ".join(v["property"] for v in res["violations"])
    assert expect in names, (mutation, res["violations"][:3])
    # a violation report must carry a replayable trail
    assert res["violations"][0]["trail"]


def test_quiesce_scope_clean_and_catches_masked_campaign():
    """The quiesce scope seeds quiesced=True states directly (natural
    entry needs e_timeout*10 idle ticks, outside the depth bound): the
    real kernel must hold quiesced_no_campaign / quiesced_no_vote, and
    a kernel whose tick path ignores the device mask must be caught."""
    res = mc.run_scope("quiesce")
    assert res["violations"] == [], res["violations"]
    assert res["scope_complete"]
    assert {"invariant:quiesced_no_campaign",
            "invariant:quiesced_no_vote"} <= set(res["properties"])

    mut = mc.run_scope("quiesce", mutation="quiesce_campaigns")
    assert mut["violations"], "quiesce_campaigns escaped the quiesce scope"
    names = " ".join(v["property"] for v in mut["violations"])
    assert "quiesced_no_campaign" in names, mut["violations"][:3]
    assert mut["violations"][0]["trail"]


def test_quiesce_scope_reaches_the_entry_and_catches_an_unsent_word():
    """The scope's third seed stands one tick short of the threshold, so
    the entry is reached naturally: the leader crosses on its own clock
    and tells both peers in that step, and a follower whose own clock is
    two thirds of the way follows the word when it is delivered.  A
    kernel whose entering lane keeps the word to itself is caught."""
    res = mc.run_scope("quiesce")
    assert res["violations"] == [], res["violations"]
    assert {"quiesce_entry_tells_peers",
            "quiesce_word_is_followed"} <= set(res["properties"])
    checker = mc.ModelChecker(scope="quiesce")
    near = next(s for s in checker.seeds() if s.trail == ("seed:near_entry",))
    leader = int(np.argmax(near.arrs["role"] == KP.LEADER))
    (_, entered), = [(a, n) for a, n in checker.successors(near)
                     if a == "tick"]
    assert entered.arrs["quiesced"].tolist() == [
        r == leader for r in range(3)]
    words = [m for m in entered.net if m[0] == int(pb.MessageType.QUIESCE)]
    assert sorted(m[2] - 1 for m in words) == [
        r for r in range(3) if r != leader]
    follower = words[0][2] - 1
    (_, followed), = [(a, n) for a, n in checker.successors(entered)
                      if a.startswith("deliver QUIESCE")
                      and a.endswith(f"->{follower + 1}")]
    assert followed.arrs["quiesced"][follower]
    assert followed.arrs["term"].tolist() == near.arrs["term"].tolist()

    mut = mc.run_scope("quiesce", mutation="quiesce_word_unsent")
    names = {v["property"] for v in mut["violations"]}
    assert "quiesce_entry_tells_peers" in names, mut["violations"][:3]
    assert mut["violations"][0]["trail"][0] == "seed:near_entry"


def test_mutation_snippets_track_kernel_source():
    src = open(os.path.join(
        REPO, "dragonboat_tpu", "core", "kernel.py")).read()
    for name, (find, replace) in mc.MUTATIONS.items():
        assert find in src, f"mutation {name!r} target drifted"
        assert find != replace


def test_drifted_snippet_raises(monkeypatch):
    monkeypatch.setitem(mc.MUTATIONS, "double_vote",
                        ("nonexistent snippet", "x"))
    with pytest.raises(RuntimeError, match="double_vote"):
        mc.load_kernel_module("double_vote")


def test_every_seeded_bug_is_caught_by_some_leg():
    """The PR's acceptance criterion in executable form: each mutation
    is owned by the model checker or by a static safety rule — none may
    fall through both legs."""
    from tests.test_safety import STATIC_OWNER

    checker_owned = {"double_vote", "quiesce_campaigns",
                     "quiesce_word_unsent"}
    assert set(mc.MUTATIONS) == checker_owned | set(STATIC_OWNER)

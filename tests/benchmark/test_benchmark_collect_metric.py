"""The reader of the part PR 38 added to the round timer (``finish.collect``:
the every-tenth-round collection as one program, one array down and one array
carried), on the hand-made run of ``test_benchmark_layers.py``: a known
registry delta gives a known value, a registry without the part (the parent's
program) gives None; its entry, found by name; it closes on the readings
around it; and a rehearsal's own registry holds what it reads.
"""

import json

import pytest

from benchmark import layers, run
from test_benchmark_layers import view
from test_benchmark_round_parts import PART_US, ROUNDS, registries

NAME = "finish_collect_ms"
#: mean microseconds a round: ~27 ms every tenth round on the parent
COLLECT_US = 2_700


def collecting_view(us=COLLECT_US):
    """The hand-made window of ``test_benchmark_round_parts.py`` with the new
    part in its registry, the earlier rounds ten times as slow."""
    before, after = registries()
    k = "engine_round_part_us.sum{part=finish.collect}"
    before[k] = 10.0 * us * 10
    after[k] = before[k] + float(us) * ROUNDS
    return view(registry_before=before, registry_after=after)


def test_reader():
    assert layers.load_reader(NAME)(collecting_view()) \
        == pytest.approx(COLLECT_US / 1e3)


def test_a_program_without_the_part_reads_nothing():
    """The parent's registry holds the seven older parts and no
    ``finish.collect``: the reader returns None and does not raise."""
    assert "finish.collect" not in PART_US
    before, after = registries()
    assert layers.load_reader(NAME)(
        view(registry_before=before, registry_after=after)) is None
    assert layers.load_reader(NAME)(view()) is None
    # no round in the window: nothing to take a mean over
    assert layers.load_reader(NAME)(
        view(registry_before=before, registry_after=dict(before))) is None


def test_it_closes_on_the_readings_around_it():
    """The collection lies inside ``finish``, beside apply and acknowledge:
    the three parts together are at most the phase."""
    v = collecting_view(us=1_500)
    got = {n: layers.load_reader(n)(v) for n in (
        NAME, "finish_apply_ms", "finish_ack_ms", "round_finish_ms")}
    assert got[NAME] + got["finish_apply_ms"] + got["finish_ack_ms"] \
        <= got["round_finish_ms"]


def test_the_metric_has_its_entry():
    """Found by name, never by position: one entry, spelled as the entries
    of its layer are, behind everything the benchmark had; the six cells
    list it (every cell collects every tenth round)."""
    with open(run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 6
    assert entries[NAME] == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": entries["round_ms"]["layer"],
        "moves": "acked_ops_per_s", "workloads": cells}
    names = list(entries)
    assert names.index(NAME) > names.index("engine_cpu_share_pct")
    # every cell it lists reports the end-to-end metric it moves
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "acked_ops_per_s")
    assert set(cells) <= set(moved.get("workloads", cells))
    assert callable(layers.load_reader(NAME))


def test_the_traced_rehearsal_prints_the_name_and_feeds_the_reader(capsys):
    """One shard, rehearsed small on the CPU backend with ``--trace 1``: the
    last line names the metric (its value withheld, as every metric's of a
    rehearsal) with ``correct`` true, and the process's own registry, which
    the reader reads on the chip, holds the part above 0 after the run: the
    rehearsal's engines collected."""
    from dragonboat_tpu import telemetry

    k = "engine_round_part_us.sum{part=finish.collect}"
    before = telemetry.GLOBAL.snapshot().get(k, 0)
    assert run.main([
        "--workload", "one-shard.write16", "--seed", str(2**31 + 38),
        "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"][NAME]["value"] is None
    assert last["metrics"][NAME]["unit"] == "ms"
    after = telemetry.GLOBAL.snapshot()
    assert after[k] > before
    rounds = after["engine_round_us.count{phase=total}"]
    assert rounds > 0 and after[k] / rounds < 1e6

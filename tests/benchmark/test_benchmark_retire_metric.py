"""The per-layer metric ``retire_columnar_pct``: its entry (the last of
``per_layer``), its reader on a hand-made registry (a known delta gives a
known share; a program without the counter, or a window in which no lane was
retired, gives None), and a rehearsal that prints it null."""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import layers, run
from test_benchmark_layers import view

NAME = "retire_columnar_pct"
COLUMNAR = "engine_retire_lanes{path=columnar}"
PER_LANE = "engine_retire_lanes{path=per_lane}"


def test_the_metric_has_its_entry_at_the_end():
    with open(run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": by_name["lane_us"]["layer"], "moves": "acked_ops_per_s"}
    assert entry["layer"] == by_name["engine_steps_per_s"]["layer"]
    # no list of cells: every cell reports acked_ops_per_s, and every
    # engine (the mesh engine inherits the pass) counts its lanes
    assert "workloads" not in entry
    assert "workloads" not in next(
        m for m in bench["end_to_end"] if m["name"] == "acked_ops_per_s")


@pytest.mark.parametrize("columnar, per_lane, want", [
    (9_900, 100, 99.0), (660, 0, 100.0), (0, 48, 0.0), (450, 50, 90.0)])
def test_the_reader_gives_the_windows_share(columnar, per_lane, want):
    """What was retired before the window (here: all of it per lane, the
    elections and bootstrap config changes of set-up) is not counted."""
    before = {COLUMNAR: 10, PER_LANE: 768}
    after = {COLUMNAR: 10 + columnar, PER_LANE: 768 + per_lane}
    assert layers.load_reader(NAME)(
        view(registry_before=before, registry_after=after)
    ) == pytest.approx(want)


@pytest.mark.parametrize("registries", [
    ({}, {}),
    ({"engine_round_lanes{what=processed}": 10},
     {"engine_round_lanes{what=processed}": 9_000}),
    ({COLUMNAR: 5}, {COLUMNAR: 50}),
    ({COLUMNAR: 5, PER_LANE: 7}, {COLUMNAR: 5, PER_LANE: 7}),
], ids=["empty", "parent", "half-a-family", "idle-window"])
def test_without_the_counter_or_a_retired_lane_the_reader_reads_nothing(
        registries):
    before, after = registries
    assert layers.load_reader(NAME)(
        view(registry_before=before, registry_after=after)) is None


def test_a_rehearsal_prints_it_null():
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main([
            "--workload", "one-shard.write16", "--seed", str(2**31 + 32),
            "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    result = json.loads(out.getvalue().splitlines()[-1])
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["metrics"][NAME] == {"value": None, "unit": "%"}

"""The per-layer metric ``retire_named_pct``: its entry (found by name, at
the end when this PR added it), its reader on a hand-made registry (a known
delta gives a known share; a program without the counter, or a window in
which no lane was retired, gives None), and a rehearsal that prints it
null."""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import layers, run
from test_benchmark_layers import view

NAME = "retire_named_pct"
DEVICE = "engine_retire_named{by=device}"
HOST = "engine_retire_named{by=host}"


def test_the_metric_has_its_entry():
    with open(run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": by_name["retire_columnar_pct"]["layer"],
        "moves": "acked_ops_per_s"}
    # behind everything the benchmark had before it
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("quiesce_wakes_per_kround")
    # no list of cells: every cell reports acked_ops_per_s, and every
    # engine (the mesh engine inherits the pass) counts who named its lanes
    assert "workloads" not in by_name[NAME]


@pytest.mark.parametrize("device, host, want", [
    (9_900, 100, 99.0), (790, 0, 100.0), (0, 48, 0.0), (450, 50, 90.0)])
def test_the_reader_gives_the_windows_share(device, host, want):
    """What was retired before the window (set-up: elections, bootstrap
    config changes, placement) is not counted."""
    before = {DEVICE: 3_072, HOST: 11}
    after = {DEVICE: 3_072 + device, HOST: 11 + host}
    assert layers.load_reader(NAME)(
        view(registry_before=before, registry_after=after)
    ) == pytest.approx(want)


@pytest.mark.parametrize("registries", [
    ({}, {}),
    ({"engine_retire_lanes{path=columnar}": 10},
     {"engine_retire_lanes{path=columnar}": 9_000}),
    ({DEVICE: 5}, {DEVICE: 50}),
    ({DEVICE: 5, HOST: 7}, {DEVICE: 5, HOST: 7}),
], ids=["empty", "parent", "half-a-family", "idle-window"])
def test_without_the_counter_or_a_retired_lane_the_reader_reads_nothing(
        registries):
    before, after = registries
    assert layers.load_reader(NAME)(
        view(registry_before=before, registry_after=after)) is None


def test_a_rehearsal_prints_it_null_and_counts_behind_it():
    from dragonboat_tpu import telemetry

    def counted():
        snap = telemetry.GLOBAL.snapshot()
        return snap.get(DEVICE, 0), snap.get(HOST, 0)

    before = counted()
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main([
            "--workload", "one-shard.write16", "--seed", str(2**31 + 36),
            "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    result = json.loads(out.getvalue().splitlines()[-1])
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["metrics"][NAME] == {"value": None, "unit": "%"}
    device, host = (a - b for a, b in zip(counted(), before))
    assert device > 0 and device > 9 * host

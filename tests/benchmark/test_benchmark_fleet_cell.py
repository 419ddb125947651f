"""The cell ``fleet.write16`` (256 shards x 3 replicas on one chip) and the
three per-layer metrics that came with it: their entries, each reader on a
hand-made registry (a known delta gives a known value; a program without the
instrument, or an idle window, gives None), and the cell rehearsed small on
the CPU backend, sound and with a control."""

import json

import pytest

from benchmark import layers, run
from test_benchmark_layers import view

NEW = ("lanes_per_round", "lane_us", "inject_batch_rows")


def registries():
    """-> (before, after): 10 rounds of 3 engines before the window, 40
    inside it.  A round of the window took 230 lanes into its per-lane
    loops and spent 30 + 10 + 52 ms in them: 400 us a lane.  Before the
    window the rounds were wider and slower (a cumulative reading would
    show); 768 replicas were injected in 48 flushes, all before it."""
    before = {"engine_round_lanes{what=processed}": 10 * 256,
              "engine_round_lanes{what=staged}": 10 * 256,
              "engine_round_us.count{phase=total}": 10,
              "engine_inject_rows": 768,
              "engine_inject_flush_us.count": 48,
              "engine_inject_flush_us.sum": 48 * 9e3}
    after = {"engine_round_lanes{what=processed}": 10 * 256 + 40 * 230,
             "engine_round_lanes{what=staged}": 10 * 256 + 40 * 240,
             "engine_round_us.count{phase=total}": 50,
             "engine_inject_rows": 768,
             "engine_inject_flush_us.count": 48,
             "engine_inject_flush_us.sum": 48 * 9e3}
    for phase, us in (("resolve", 30_000), ("save", 10_000),
                      ("finish", 52_000), ("fetch", 60_000)):
        before[f"engine_round_us.sum{{phase={phase}}}"] = 10 * 10.0 * us
        after[f"engine_round_us.sum{{phase={phase}}}"] = (
            10 * 10.0 * us + 40.0 * us)
    return before, after


def fleet_view():
    before, after = registries()
    return view(registry_before=before, registry_after=after)


def test_the_cell_and_its_metrics_have_their_entries():
    with open(run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == "fleet.write16")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fleet", "write16", 1)
    config = next(c for c in bench["configs"] if c["name"] == "fleet")
    assert config["file"] == "benchmark/configs/fleet.json"
    assert config["reduced"] == ["servers", "shards"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = list(entries)
    for name in NEW:
        assert "workloads" not in entries[name], (
            f"{name} is read at every width: 1, 48, 144 and 256 lanes")
    assert [entries[n]["moves"] for n in NEW] == [
        "acked_ops_per_s", "acked_ops_per_s", "setup_s"]
    assert [entries[n]["source"] for n in NEW] == [
        "program_counter", "program_span", "program_counter"]
    assert entries["lanes_per_round"]["layer"] == entries["lane_us"][
        "layer"] == entries["engine_steps_per_s"]["layer"]
    assert entries["inject_batch_rows"]["layer"] == entries[
        "start_replica_ms"]["layer"]
    # appended as one block, in this order, behind what was there (the
    # block is found by where it starts, so a later PR may append)
    at = names.index(NEW[0])
    assert tuple(names[at:at + 3]) == NEW
    assert at > names.index("mesh_hub_msgs_per_step") > names.index(
        "add_shard_lock_ms") > names.index("round_ms")
    # every end-to-end metric without a list is this cell's too
    assert {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m} == {"acked_ops_per_s", "setup_s"}


def test_readers_on_a_known_registry():
    v = fleet_view()
    assert layers.load_reader("lanes_per_round")(v) == pytest.approx(230.0)
    assert layers.load_reader("lane_us")(v) == pytest.approx(
        (30_000 + 10_000 + 52_000) / 230)
    assert layers.load_reader("inject_batch_rows")(v) == pytest.approx(16.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_instrument_reads_nothing(name):
    """The parent commit's registry lacks the new keys (it has the round
    timer's): the reader returns None and does not raise."""
    before, after = registries()
    old = [{k: v for k, v in r.items()
            if not k.startswith(("engine_round_lanes", "engine_inject_rows"))}
           for r in (before, after)]
    assert layers.load_reader(name)(
        view(registry_before=old[0], registry_after=old[1])) is None
    assert layers.load_reader(name)(view()) is None
    assert layers.load_reader(name)(
        view(registry_before={}, registry_after={})) is None


def test_an_idle_window_reads_only_the_cumulative_one():
    before, _ = registries()
    v = view(registry_before=before, registry_after=dict(before))
    assert layers.load_reader("lanes_per_round")(v) is None
    assert layers.load_reader("lane_us")(v) is None
    assert layers.load_reader("inject_batch_rows")(v) == pytest.approx(16.0)
    # rounds that processed no lane (idle engines' ticks): no lane to
    # divide by
    after = dict(before)
    after["engine_round_us.count{phase=total}"] += 5
    assert layers.load_reader("lanes_per_round")(
        view(registry_before=before, registry_after=after)) == 0.0
    assert layers.load_reader("lane_us")(
        view(registry_before=before, registry_after=after)) is None


# -- the cell, rehearsed -------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_episodes():
    """One fleet of six shards on the CPU backend: a sound traced episode,
    then a control."""
    import io
    from contextlib import redirect_stdout

    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main([
            "--workload", "fleet.write16", "--seed", "31", "--seconds", "2",
            "--trace", "1", "--rehearse",
            "--episodes", f"{2**31 + 31},32:lost-write"]) == 0
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    return ({line["fault"]: line for line in lines
             if line.get("phase") == "episode"},
            next(line for line in lines if line.get("phase") == "deployed"))


def test_fleet_cell_rehearsal_is_correct(fleet_episodes):
    episodes, deployed = fleet_episodes
    assert (deployed["config"], deployed["shards"], deployed["replicas"]) == (
        "fleet", 6, 3)
    assert deployed["shards_led_by_host"] == {"1": 2, "2": 2, "3": 2}
    assert "warm_row_fetch_s" not in deployed
    sound = episodes[None]
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(NEW) | {"round_ms", "round_resolve_ms", "round_save_ms",
                       "round_finish_ms", "engine_steps_per_s",
                       "acked_per_step", "admission_fill_pct",
                       "start_replica_ms", "add_shard_lock_ms"} <= set(
        sound["metrics"])
    units = {name: sound["metrics"][name]["unit"] for name in NEW}
    assert units == {"lanes_per_round": "lanes/round", "lane_us": "us/lane",
                     "inject_batch_rows": "rows/flush"}
    assert all(sound["metrics"][name]["value"] is None for name in NEW)
    # dwell metrics list their cells and this is none of them
    assert "fsync_ms" not in sound["metrics"]


def test_fleet_cell_control_comes_out_not_correct(fleet_episodes):
    episodes, _ = fleet_episodes
    assert episodes["lost-write"]["correct"] is False
    assert episodes["lost-write"]["attempted"] > 0
